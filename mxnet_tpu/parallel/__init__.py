"""Parallelism: meshes, collectives, and fused distributed training steps.

This package is the TPU-native replacement for the reference's entire
distributed stack (src/kvstore/comm.h device reduce, kvstore_nccl.h RCCL
rings, ps-lite parameter server): parallelism is expressed as shardings over
a jax.sharding.Mesh and compiled into XLA programs whose collectives ride
ICI/DCN (SURVEY §2.4, §5.8).
"""
from .mesh import (make_mesh, local_mesh, device_mesh, host_barrier,
                   global_allreduce)
from .async_loss import AsyncLoss, InflightRing, drain_all, inflight_limit
from .data_parallel import (DataParallelStep, compile_step_with_plan,
                            make_train_step)
from .plan import (Plan, dp_plan, tensor_parallel_plan, pipeline_plan,
                   ring_plan, ulysses_plan)
from .ring import ring_attention, ring_self_attention
from .ulysses import ulysses_self_attention
from .pipeline import pipeline_apply
from .scope import ring_attention_scope, ring_scope, ring_scope_mesh
from . import dist
from . import planner
from . import sharding
