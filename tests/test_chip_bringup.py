"""What the chip bring-up decided, checked from the CPU in seconds.

* a TPU context never computes on the host: ``mx.tpu()`` raises without an
  accelerator;
* one compile-cache rule: a set JAX_COMPILATION_CACHE_DIR is left alone,
  unset the cache is one fixed directory inside the checkout; and a
  restarted process loads every program of the five jit sites from it;
* every exported Pallas kernel, and the serving engine's decode step under
  the decision ``_serve_fused()`` takes, LOWERS for the TPU.  Pallas-to-Mosaic
  lowering runs in jaxlib, so ``jax.export`` for platform "tpu" exercises it
  here without libtpu or a chip (this is the test that would have caught the
  paged kernel's head-batched dot);
* Mosaic calls are not selected where GSPMD would have to partition them;
* ``chip_smoke.py`` fails, and prints no result, without a TPU;
* the fused step neither retraces on step 2 nor shares buffers it donates.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import pallas

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BF16 = jnp.bfloat16


def _mosaic_calls(fn, *structs, partitioned=False):
    """Kernel names of the Mosaic calls in ``fn`` lowered for the TPU."""
    import re

    with pallas.compute_on("tpu", partitioned):
        exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(*structs)
    return re.findall(r'kernel_name = "(\w+)"', exp.mlir_module())


def _s(shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# contexts
# ---------------------------------------------------------------------------
def test_accelerator_context_raises_on_cpu_only_process():
    for ctx in (mx.tpu(), mx.gpu(), mx.tpu(3)):
        with pytest.raises(MXNetError, match="no accelerator"):
            ctx.jax_device
    assert mx.cpu().jax_device.platform == "cpu"
    assert mx.num_tpus() == 0


# ---------------------------------------------------------------------------
# the compile-cache rule (fresh interpreters: the rule runs at import)
# ---------------------------------------------------------------------------
def test_compile_cache_rule():
    code = ("import jax, mxnet_tpu; "
            "print(jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    procs = [subprocess.Popen([sys.executable, "-c", code], env=e, cwd="/",
                              stdout=subprocess.PIPE, text=True)
             for e in (env, dict(env, JAX_COMPILATION_CACHE_DIR="/some/dir"))]
    unset, was_set = (p.communicate(timeout=120)[0].strip() for p in procs)
    assert all(p.returncode == 0 for p in procs)
    assert unset == os.path.join(_REPO, ".jax_cache")
    assert was_set == "/some/dir"
    with open(os.path.join(_REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ---------------------------------------------------------------------------
# a restart goes through that one cache: a second process under the same
# JAX_COMPILATION_CACHE_DIR, with the thresholds benchmark/run.py sets, loads
# every program of the five jit sites and compiles none (what the benchmark's
# first_setup_s -> setup_s factor rests on)
# ---------------------------------------------------------------------------
_RESTART_CHILD = r'''
import hashlib
import json
import jax, jax.monitoring
import numpy as np

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
seen = {"hits": 0, "misses": 0, "compiles": 0, "loads": 0}
NAMES = {"/jax/compilation_cache/cache_hits": "hits",
         "/jax/compilation_cache/cache_misses": "misses",
         "/jax/core/compile/backend_compile_duration": "compiles",
         "/jax/compilation_cache/cache_retrieval_time_sec": "loads"}


def on(event, *_a, **_kw):
    if event in NAMES:
        seen[NAMES[event]] += 1


jax.monitoring.register_event_listener(on)
jax.monitoring.register_event_duration_secs_listener(on)

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.gluon import nn
from mxnet_tpu.models.transformer import Transformer
from mxnet_tpu.optimizer.fused import FusedUpdater
from mxnet_tpu.parallel import DataParallelStep, local_mesh
from mxnet_tpu.serving import Request, ServingEngine, TransformerAdapter


def dense_net():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu", in_units=4),
            nn.Dense(2, in_units=8))
    net.initialize(mx.init.Xavier())
    return net


X = nd.array(np.linspace(-1, 1, 32).reshape(8, 4).astype(np.float32))


def step_site():
    step = DataParallelStep(
        dense_net(), gluon.loss.L2Loss(),
        mesh=local_mesh(devices=[jax.devices("cpu")[0]]), optimizer="adam",
        optimizer_params={"learning_rate": 0.01})
    loss = step.step(X, nd.zeros((8, 2))).asnumpy()
    step.drain()
    return [loss] + [np.asarray(v) for _, v in sorted(step.params.items())]


def forward_site():
    net = dense_net()
    net.hybridize()
    return [net(X).asnumpy()]


def fused_site():
    upd = FusedUpdater(mx.optimizer.create("sgd", learning_rate=0.1,
                                           momentum=0.9))
    ws = [nd.ones((4, 3)), nd.ones((5,)) * 2]
    upd.apply([(i, w * 0.5, w) for i, w in enumerate(ws)])
    assert upd.last_info["n_fused"] == 2, upd.last_info
    return [w.asnumpy() for w in ws]


def reduce_site():
    kv = mx.kvstore.create("device")
    ctxs = [mx.cpu(i) for i in range(4)]
    kv.init("w", nd.zeros((3, 4), ctx=ctxs[0]))
    kv.push("w", [nd.ones((3, 4), ctx=c) * (i + 1)
                  for i, c in enumerate(ctxs)])
    out = nd.zeros((3, 4), ctx=ctxs[0])
    kv.pull("w", out)
    return [out.asnumpy()]


def decode_site():
    net = Transformer(16, units=32, hidden_size=64, num_heads=4,
                      num_layers=2, max_length=48, dropout=0.0)
    net.initialize(mx.init.Xavier())
    eng = ServingEngine(TransformerAdapter(net, src_max_len=6), slots=2,
                        page_size=4, max_len=8, stream_every=2)
    out = eng.serve([Request(np.array([3, 7, 11, 5]), max_new_tokens=5,
                             bos_id=1, eos_id=2)])
    return [np.asarray(list(out.values())[0])]


report = {}
for name, site in [("step", step_site), ("forward", forward_site),
                   ("fused_update", fused_site), ("reduce", reduce_site),
                   ("decode", decode_site)]:
    mx.random.seed(0)
    before = dict(seen)
    digest = hashlib.sha256()
    for a in site():
        digest.update(np.ascontiguousarray(a).tobytes())
    report[name] = dict({k: seen[k] - before[k] for k in seen},
                        result=digest.hexdigest())
print("RESTART " + json.dumps(report))
'''

_RESTART_SITES = ("step", "forward", "fused_update", "reduce", "decode")


@pytest.fixture(scope="module")
def restart_pair(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_COMPILATION_CACHE_DIR=str(
                   tmp_path_factory.mktemp("restart_cache")))
    reports = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", _RESTART_CHILD], env=env,
                             capture_output=True, text=True, timeout=400)
        assert out.returncode == 0, out.stderr[-3000:]
        (line,) = [ln for ln in out.stdout.splitlines()
                   if ln.startswith("RESTART ")]
        reports.append(json.loads(line[len("RESTART "):]))
    return reports


@pytest.mark.parametrize("site", _RESTART_SITES)
def test_a_restarted_process_loads_every_program_from_the_one_cache(
        restart_pair, site):
    cold, warm = (r[site] for r in restart_pair)
    assert cold["misses"] > 0, cold
    assert warm["misses"] == 0, warm
    assert warm["hits"] == cold["hits"] + cold["misses"], (cold, warm)
    # the events compiles_in_window counts: each "compile" was a load
    assert warm["loads"] == warm["compiles"] == warm["hits"], warm
    assert warm["result"] == cold["result"]


# ---------------------------------------------------------------------------
# kernels lower through Mosaic
# ---------------------------------------------------------------------------
def test_flash_attention_lowers_for_tpu_forward_and_backward():
    def loss(q, k, v):
        return pallas.flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    q = _s((4, 256, 64))
    calls = _mosaic_calls(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert sorted(set(calls)) == ["mx_flash_dkv", "mx_flash_dq",
                                  "mx_flash_fwd"]


def test_grouped_query_flash_attention_lowers_at_the_published_heads():
    """32 query heads over 2 key-value heads of 128 at 8,192 positions (the
    nemotron_h attention layer): three Mosaic calls, K and V at their own
    head count in the lowered module (no 16-fold repeat)."""
    def loss(q, k, v):
        return pallas.flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    q, kv = _s((1, 32, 8192, 128)), _s((1, 2, 8192, 128))
    with pallas.compute_on("tpu"):
        exp = jax.export.export(jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                                platforms=["tpu"])(q, kv, kv)
    text = exp.mlir_module()
    import re

    assert sorted(set(re.findall(r'kernel_name = "(\w+)"', text))) == [
        "mx_flash_dkv", "mx_flash_dq", "mx_flash_fwd"]
    assert "32x8192x128xbf16" in text and "2x8192x128xbf16" in text
    assert [tuple(o.shape) for o in exp.out_avals] == [
        (1, 32, 8192, 128), (1, 2, 8192, 128), (1, 2, 8192, 128)]


def test_chunked_scan_lowers_as_its_kernels_at_the_published_shapes():
    """The scan's gradient at the Nemotron cell's shapes (64 heads of 64 over
    8 groups, state 128, 2 x 8,192 positions in chunks of 128): only
    ``mx_ssd_*`` Mosaic calls, each with an operand or result the cell's
    ``ssd_scan`` trace pattern finds (HLO prints ``f32[2,64,8,8,...]``), and
    outside the calls no array of batch x chunks x heads x 128 x 128 elements
    and no f32 copy of x or y."""
    import re

    from mxnet_tpu.ops import ssm_ops

    def grads(ct, *args):
        y, vjp = jax.vjp(lambda *a: ssm_ops.ssd_scan(*a, chunk=128), *args)
        return (y,) + vjp(ct)

    x, bc = _s((2, 8192, 64, 64)), _s((2, 8192, 8, 128))
    head = _s((64,), jnp.float32)
    with pallas.compute_on("tpu"):
        exp = jax.export.export(jax.jit(grads), platforms=["tpu"])(
            x, x, _s((2, 8192, 64)), head, bc, bc, head, head)
    text = exp.mlir_module()
    calls = [ln for ln in text.splitlines() if "kernel_name" in ln]
    assert [re.search(r'kernel_name = "(\w+)"', ln).group(1)
            for ln in calls] == ["mx_ssd_fwd", "mx_ssd_bwd"]
    with open(os.path.join(_REPO, "benchmark", "checks",
                           "nemotron_twotower_30b_a3b.train_2x8k.json")) as f:
        pattern = json.load(f)["kernels"]["ssd_scan"]

    def as_hlo(mlir_type):              # 2x64x8x8x64x128xf32 -> f32[2,64,...]
        *dims, dtype = mlir_type.split("x")
        return f"{dtype}[{','.join(dims)}]"

    for ln in calls:
        operands, results = re.search(
            r" : \((.*?)\) -> \(?(.*?)\)? loc", ln).groups()
        shapes = [as_hlo(t) for t in re.findall(r"tensor<([\w]+)>",
                                                operands + results)]
        assert "f32[2,64,8,8,64,128]" in shapes      # the chunks' start states
        assert re.search(pattern, f"%mx_ssd.1 = ({', '.join(shapes)}) "
                                  f"custom-call()")
    outside = "\n".join(ln for ln in text.splitlines()
                        if "kernel_name" not in ln)
    sizes = {int(np.prod([int(d) for d in t.split("x")[:-1]]))
             for t in re.findall(r"tensor<([0-9x]+x[a-z]\w*)>", outside)}
    assert max(sizes) < 2 * 64 * 64 * 128 * 128
    assert not re.search(r"tensor<2x8192x(4096|64x64)xf32>", outside)
    assert [tuple(o.shape) for o in exp.out_avals][:2] == [
        (2, 8192, 64, 64)] * 2
    assert exp.out_avals[0].dtype == BF16


def test_the_stream_mix_lowers_as_its_kernels_at_the_cells_shapes():
    """A hyper-connected sublayer's gradient at the Xing cell's shapes (4
    streams of 3,584, 4,096 tokens, bf16): only ``mx_mhc_*`` Mosaic calls,
    each found by the cell's UNCHANGED ``mhc_mix`` trace pattern and by none
    of the other three; no loop is left (Sinkhorn's iterations are inside
    ``mx_mhc_coef`` and ``mx_mhc_coef_pre_bwd``) and outside the calls no
    sum over a stream's lanes into one f32 a token."""
    import re

    from mxnet_tpu.ops import hc_ops

    def grads(ct, x, gain, phi, a, b, w):
        def sublayer(x, gain, phi, a, b, w):
            c = hc_ops.mhc_coefficients(x, gain, phi, a, b)
            u = hc_ops.mhc_pre(x, c)
            return hc_ops.mhc_post(x, u * w, c)

        out, vjp = jax.vjp(sublayer, x, gain, phi, a, b, w)
        return (out,) + vjp(ct)

    x = _s((1, 4096, 14336))
    with pallas.compute_on("tpu"):
        exp = jax.export.export(jax.jit(grads), platforms=["tpu"])(
            x, x, _s((14336,)), _s((24, 14336)), _s((3,)), _s((24,)),
            _s((3584,)))
    text = exp.mlir_module()
    calls = [ln for ln in text.splitlines() if "kernel_name" in ln]
    names = [re.search(r'kernel_name = "(\w+)"', ln).group(1) for ln in calls]
    assert names == ["mx_mhc_coef", "mx_mhc_pre", "mx_mhc_post",
                     "mx_mhc_post_bwd", "mx_mhc_coef_pre_bwd"]
    with open(os.path.join(_REPO, "benchmark", "checks",
                           "xing4_0_29b_a4b.train_1x4k.json")) as f:
        patterns = json.load(f)["kernels"]

    def as_hlo(mlir_type):              # 1x4096x24xf32 -> f32[1,4096,24]
        *dims, dtype = mlir_type.split("x")
        return f"{dtype}[{','.join(dims)}]"

    for name, ln in zip(names, calls):
        operands, results = re.search(
            r" : \((.*?)\) -> \(?(.*?)\)? loc", ln).groups()
        shapes = [as_hlo(t) for t in re.findall(r"tensor<([\w]+)>",
                                                operands + results)]
        assert "bf16[1,4096,14336]" in shapes
        hlo = f"%{name}.1 = ({', '.join(shapes)}) custom-call()"
        assert re.search(patterns["mhc_mix"], hlo)
        for other in ("mla_front", "moe_experts", "flash_attention"):
            assert not re.search(patterns[other], hlo)
    outside = "\n".join(ln for ln in text.splitlines()
                        if "kernel_name" not in ln)
    assert "stablehlo.while" not in outside
    assert "4x4x4096xf32" not in outside
    # a reduction over lanes to one f32 a token would print this result
    assert not re.search(r"stablehlo.reduce.*-> tensor<(1x)?4096xf32>",
                         outside)
    assert [tuple(o.shape) for o in exp.out_avals] == [
        (1, 4096, 14336), (1, 4096, 14336), (14336,), (24, 14336), (3,),
        (24,), (3584,)]


def test_expert_products_lower_as_grouped_kernels_at_the_published_widths():
    from mxnet_tpu.ops import moe_ops

    def loss(x, idx, w, up, down):
        out, _landed = moe_ops.moe_experts(x, idx, w, up, down, first=0)
        return out.astype(jnp.float32).sum()

    calls = _mosaic_calls(
        jax.grad(loss, argnums=(0, 3, 4)), _s((1024, 2688)),
        _s((1024, 6), jnp.int32), _s((1024, 6), jnp.float32),
        _s((8, 2688, 1856)), _s((8, 1856, 2688)))
    # megablox gmm and tgmm, and the combine of ops/pallas/moe_rows.py
    assert len(calls) >= 5 and set(calls) == {"kernel", "mx_moe_combine"}


def _hlo_computations(exported):
    """{name: lines} of the exported module as HLO text, and for a
    computation the lines of everything it reaches through calls."""
    import re

    from jax._src.lib import xla_client as xc

    hlo = xc._xla.mlir.mlir_module_to_xla_computation(
        exported.mlir_module(), use_tuple_args=False,
        return_tuple=False).as_hlo_text()
    comps, name = {}, None
    for ln in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?([\w.\-]+) (?:\(.*\) -> .* )?\{$", ln)
        if head:
            name = head.group(1)
            comps[name] = []
        elif ln.startswith("}"):
            name = None
        elif name is not None:
            comps[name].append(ln)

    def reach(start):
        seen, todo = [], [start]
        while todo:
            at = todo.pop()
            if at in seen:
                continue
            seen.append(at)
            for ln in comps[at]:
                for group in re.findall(
                        r"(?:to_apply|body|condition|calls)=([\w.\-]+)|"
                        r"branch_computations=\{([^}]*)\}", ln):
                    todo += [n.strip() for g in group if g
                             for n in g.split(",")]
        return {n: comps[n] for n in seen}

    return comps, reach


def test_the_experts_loops_keep_their_work_inside_at_the_cells_shapes():
    """Forward and backward of the held experts at the Nemotron cell's
    shapes (16,384 tokens of 2,688, 8 held of 128, 6 choices), lowered for
    the TPU: both loops are the ``while`` instructions the cell's
    ``moe_experts`` pattern finds, the grouped products and the combine are
    inside their bodies, and no body scatters rows or fills an array as
    large as an accumulator: a chunk adds into the loop's carry."""
    import re

    from mxnet_tpu.ops import moe_ops

    def both(ct, x, idx, w, up, down):
        out, vjp = jax.vjp(lambda x, w, up, down: moe_ops.moe_experts(
            x, idx, w, up, down, first=0)[0], x, w, up, down)
        return (out,) + vjp(ct)

    tokens = 16384
    with pallas.compute_on("tpu"):
        exp = jax.export.export(jax.jit(both), platforms=["tpu"])(
            _s((tokens, 2688)), _s((tokens, 2688)),
            _s((tokens, 6), jnp.int32), _s((tokens, 6), jnp.float32),
            _s((8, 2688, 1856)), _s((8, 1856, 2688)))
    assert set(re.findall(r'kernel_name = "(\w+)"', exp.mlir_module())) == {
        "kernel", "mx_moe_combine"}
    with open(os.path.join(_REPO, "benchmark", "checks",
                           "nemotron_twotower_30b_a3b.train_2x8k.json")) as f:
        pattern = json.load(f)["kernels"]["moe_experts"]
    comps, reach = _hlo_computations(exp)
    loops = [ln for lines in comps.values() for ln in lines
             if " while(" in ln and re.search(pattern, "%" + ln.strip())]
    assert len(loops) == 2                       # forward, backward
    wide = r"(16384,2688|16384,1856|8,2688,1856|8,1856,2688)"
    seen = []
    for loop in loops:
        body = reach(re.search(r"body=([\w.\-]+)", loop).group(1))
        lines = [ln for ls in body.values() for ln in ls]
        kernels = [re.sub(r"[_.]\d.*$", "", n) for n, ls in body.items()
                   if any('"tpu_custom_call"' in ln for ln in ls)]
        seen.append(sorted(kernels))
        # rows are gathered (XLA's gather runs at the memory's pace), never
        # scattered, and nothing of an accumulator's size is filled
        assert not [ln for ln in lines
                    if re.search(r"\[" + wide + r"\]\S* scatter\(", ln)]
        assert not [ln for ln in lines if re.search(
            r"\[(16384,2688|8,2688,1856|8,1856,2688)\]\S* broadcast\("
            r".*dimensions=\{\}", ln)]
    # by jitted function: up and down; those, their transposes, the two
    # weight gradients
    assert sorted(seen) == [["combine"] + ["gmm"] * 2,
                            ["combine"] + ["gmm"] * 4 + ["tgmm"] * 2]


def test_layer_norm_kernels_lower_for_tpu():
    x, g = _s((64, 768)), _s((768,))
    assert _mosaic_calls(pallas.layer_norm, x, g, g) == ["mx_layer_norm_fwd"]
    assert _mosaic_calls(pallas.add_layer_norm, x, x, g, g) == [
        "mx_add_layer_norm_fwd"]


def test_softmax_cross_entropy_lowers_and_sizes_rows_from_width():
    from mxnet_tpu.ops.pallas import fused

    calls = _mosaic_calls(pallas.softmax_cross_entropy, _s((1024, 30522)),
                          _s((1024,), jnp.int32))
    assert calls == ["mx_softmax_xent"]
    # the double-buffered (bn, C) block stays inside the budget at the
    # repo's own vocabulary, and narrow rows keep the 256-row block
    wide = fused._row_block(1024, fused._lane_bytes(30522, BF16))
    assert 8 <= wide < 256 and wide % 8 == 0
    assert 2 * wide * fused._lane_bytes(30522, BF16) <= fused._BLOCK_VMEM_BYTES
    assert fused._row_block(16384, fused._lane_bytes(768, BF16, BF16)) == 256
    assert fused._row_block(5, fused._lane_bytes(768, BF16)) == 8


def test_paged_kernel_is_not_selected_and_fails_loudly_when_forced(
        monkeypatch):
    from mxnet_tpu.serving.engine import _serve_fused

    monkeypatch.delenv("MX_SERVE_FLASH", raising=False)
    with pallas.compute_on("tpu"):
        assert _serve_fused() is False
    monkeypatch.setenv("MX_SERVE_FLASH", "1")
    assert _serve_fused() is True
    # forcing it reaches Mosaic lowering, which rejects the head-batched
    # dot (ROADMAP A4 rewrites the kernel page-blocked)
    with pytest.raises(Exception, match="(?i)non.contracting|mosaic|lower"):
        _mosaic_calls(pallas.paged_decode_attention, _s((4, 4, 64),
                                                        jnp.float32),
                      _s((9, 16, 4, 64), jnp.float32),
                      _s((9, 16, 4, 64), jnp.float32),
                      _s((4, 3), jnp.int32), _s((4,), jnp.int32))


def test_serving_decode_step_lowers_for_tpu_with_default_settings(
        monkeypatch):
    """The engine as the chip would build it (fused-kernel pass resolved
    for "tpu", MX_SERVE_FLASH unset): prefill and decode lower for the TPU,
    with Mosaic layer norms and without the paged kernel."""
    from mxnet_tpu.models.transformer import Transformer
    from mxnet_tpu.serving import ServingEngine, TransformerAdapter

    monkeypatch.delenv("MX_SERVE_FLASH", raising=False)
    monkeypatch.delenv("MX_PALLAS_FUSED", raising=False)
    net = Transformer(64, units=32, hidden_size=64, num_heads=2,
                      num_layers=1)
    net.initialize(mx.init.Xavier())
    with pallas.compute_on("tpu"):
        eng = ServingEngine(TransformerAdapter(net, src_max_len=8), slots=4,
                            page_size=8, max_len=16)
        assert eng._adapter._resolved_fused() is False
        assert eng._pipeline.get("fused_kernels") is not None
    eng._adapter.warmup(eng._ctx)
    params = tuple(_s(a.shape, a.dtype) for a in eng._params())
    state = tuple(_s(a.shape, a._data.dtype) for a in eng._state.values())
    decode = _mosaic_calls(eng._traced(eng._decode_body), params, *state)
    assert decode and set(decode) <= {"mx_layer_norm_fwd",
                                      "mx_add_layer_norm_fwd"}

    def prefill(nds):
        from mxnet_tpu import ndarray as F

        out = eng._adapter.prefill(F, nds[0])
        return [out[k] for k in eng._adapter.prefill_names]

    assert _mosaic_calls(eng._traced(prefill), params,
                         _s((1, 8), jnp.int32))


# ---------------------------------------------------------------------------
# Mosaic calls and GSPMD
# ---------------------------------------------------------------------------
def test_kernels_are_not_selected_where_gspmd_partitions():
    from jax.sharding import Mesh, PartitionSpec as P

    from mxnet_tpu.ops.pallas import registry

    with pallas.compute_on("tpu"):
        assert pallas.use_compiled() and not pallas.interpret()
        assert registry.substitution("LayerNorm") is not None
    with pallas.compute_on("tpu", partitioned=True):
        assert not pallas.use_compiled()
        assert registry.substitution("LayerNorm") is None
        with pytest.raises(MXNetError, match="shard_map"):
            pallas.interpret()
        # the stock op, not a Mosaic call, inside the partitioned program
        x, g = _s((8, 16, 128)), _s((128,))
        assert _mosaic_calls(lambda x, g, b: nd.LayerNorm(
            nd.NDArray(x), nd.NDArray(g), nd.NDArray(b))._data,
            x, g, g, partitioned=True) == []
        # the experts' chunk loop: ragged_dot and a scatter-add there
        from mxnet_tpu.ops import moe_ops

        assert not moe_ops._kernels(16384, 128)
        assert _mosaic_calls(
            jax.grad(lambda x, idx, w, up, down: moe_ops.moe_experts(
                x, idx, w, up, down)[0].astype(jnp.float32).sum(),
                argnums=(0, 3, 4)),
            _s((1024, 256)), _s((1024, 6), jnp.int32),
            _s((1024, 6), jnp.float32), _s((8, 256, 128)),
            _s((8, 128, 256)), partitioned=True) == []
        # the stream mix: the jax form, Sinkhorn's scan and all
        from mxnet_tpu.ops import hc_ops

        def mix(x, gain, phi, a, b, y):
            c = hc_ops.mhc_coefficients(x, gain, phi, a, b)
            return hc_ops.mhc_post(x, y * hc_ops.mhc_pre(x, c), c)

        assert hc_ops._kernels(_s((8, 512)), 4, 24) is None
        assert _mosaic_calls(
            jax.grad(lambda *args: mix(*args).astype(jnp.float32).sum(),
                     argnums=(0, 1, 2, 3, 4)),
            _s((2, 64, 512)), _s((512,)), _s((24, 512)), _s((3,)), _s((24,)),
            _s((2, 64, 128)), partitioned=True) == []
        # a shard_map body is per-device code again
        seen = []
        mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))

        def body(x):
            seen.append(pallas.use_compiled())
            return x

        jax.eval_shape(jax.shard_map(body, mesh=mesh, in_specs=P("dp"),
                                     out_specs=P("dp")), _s((4, 8)))
        assert seen == [True]
    # interpreted kernels (off-TPU) partition like any HLO
    with pallas.compute_on("cpu", partitioned=True):
        assert pallas.interpret() is True
        assert registry.substitution("LayerNorm") is not None


def test_host_context_ops_trace_for_the_host():
    """On a TPU host an eager op on ``mx.cpu()`` must not trace the kernel
    the default backend would get (the chip's consistency sweep met "Only
    interpret mode is supported on CPU backend" in LayerNorm): the per-op
    jit is keyed by the context's platform."""
    from mxnet_tpu.ops.registry import get_op

    op, attrs = get_op("LayerNorm"), {"axis": -1, "eps": 1e-5}
    host, default = op.jitted(attrs, "cpu"), op.jitted(attrs, None)
    assert host is not default and host is op.jitted(attrs, "cpu")
    x, g = _s((16, 128)), _s((128,))
    with pallas.compute_on("tpu"):  # the process default, as on the chip
        on_host = jax.export.export(host, platforms=["cpu"])(x, g, g)
        on_chip = jax.export.export(default, platforms=["tpu"])(x, g, g)
    assert "tpu_custom_call" not in on_host.mlir_module()
    assert 'kernel_name = "mx_layer_norm_fwd"' in on_chip.mlir_module()


# ---------------------------------------------------------------------------
# the smoke itself
# ---------------------------------------------------------------------------
def test_chip_smoke_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(_REPO,
                                                       "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.splitlines()[0] == "platform: cpu"
    for line in out.stdout.splitlines():
        with pytest.raises(ValueError):  # no JSON result on any line
            json.loads(line)


# ---------------------------------------------------------------------------
# what donation and a second compile would have cost on the chip
# ---------------------------------------------------------------------------
def test_fused_step_owns_its_params_and_traces_once():
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    net = gluon.nn.Dense(4, in_units=8)
    net.initialize(mx.init.Xavier())
    step = DataParallelStep(
        net, gluon.loss.L2Loss(), mesh=local_mesh(devices=jax.devices()[:1]),
        optimizer="adam", optimizer_params={"learning_rate": 1e-2})
    x, y = nd.ones((2, 8)), nd.ones((2, 4))
    for _ in range(3):
        step.step(x, y)
    step.drain()
    # Adam's step counter starts on the mesh: step 2 reuses step 1's
    # executable (on the chip a retrace doubled BERT's compile time)
    assert step._jitted._cache_size() == 1
    # the step donates its params on an accelerator; a buffer shared with
    # the block would leave the Gluon Parameter deleted after step 1 (the
    # four-chip run met exactly that: device_put aliases the source
    # device's shard of a replicated array)
    mine = {p.data()._data.unsafe_buffer_pointer()
            for p in net.collect_params().values()}

    def held(s):
        return {sh.data.unsafe_buffer_pointer() for a in s.params.values()
                for sh in a.addressable_shards}

    assert not mine & held(step)
    step4 = DataParallelStep(
        net, gluon.loss.L2Loss(), mesh=local_mesh(devices=jax.devices()[:4]))
    step4._ensure_state((x,))
    assert len(held(step4)) == 4 * len(mine) and not mine & held(step4)
