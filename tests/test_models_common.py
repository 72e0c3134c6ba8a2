"""``models/common.py`` ``checkpointed`` under the rule of
``ops/recompute.py``, at small sizes on the CPU: a recomputed layer runs the
flash kernel once, keeps the kernel's output and row sums and its products
with a weight, makes norms and the kernel's operands again, and reports what
it keeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu.gluon import HybridBlock, nn
from mxnet_tpu.models.common import checkpointed
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.ops import recompute, registry
from mxnet_tpu.ops.pallas import flash_attention

B, L, D, HEADS = 1, 8, 16, 2
F32 = 4


class _Layer(HybridBlock):
    """x + W_o flash(norm(W_q u), norm(W_k u), W_v u), u = norm(x): the
    kernel's operands are elementwise results, not products."""

    def __init__(self, prefix):
        super().__init__(prefix=prefix)
        with self.name_scope():
            self.norm = nn.RMSNorm(in_channels=D, prefix="norm_")
            self.q_norm = nn.RMSNorm(in_channels=D, prefix="q_norm_")
            self.k_norm = nn.RMSNorm(in_channels=D, prefix="k_norm_")
            self.q, self.k, self.v, self.o = (
                nn.Dense(D, use_bias=False, flatten=False, in_units=D,
                         prefix=p) for p in ("q_", "k_", "v_", "o_"))

    def hybrid_forward(self, F, x):
        u = self.norm(x)

        def attend(q, k, v):
            heads = lambda t: t.reshape(B, L, HEADS, -1).transpose(0, 2, 1, 3)
            o = flash_attention(heads(q), heads(k), heads(v), causal=True)
            return o.transpose(0, 2, 1, 3).reshape(B, L, D)

        o = registry.invoke_fn(attend, [self.q_norm(self.q(u)),
                                        self.k_norm(self.k(u)), self.v(u)])
        return x + self.o(o)


@pytest.fixture(scope="module")
def stack():
    mx.random.seed(3)
    layers = [_Layer(f"layer{i}_") for i in range(2)]
    for layer in layers:
        layer.initialize(mx.init.Normal(0.3), ctx=mx.cpu())

    def loss(x, wrap=checkpointed):
        h = NDArray(x, ctx=mx.cpu())
        for layer in layers:
            h = wrap(layer, h)
        return jnp.sum(jnp.square(h._data))

    x = jnp.asarray(np.random.default_rng(0).normal(size=(B, L, D)),
                    jnp.float32)
    return loss, x


def _kernels(jaxpr):
    """name -> how many Pallas calls of it the jaxpr holds at any depth."""
    counts = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            counts[name] = counts.get(name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            for name, n in _kernels(sub).items():
                counts[name] = counts.get(name, 0) + n
    return counts


@pytest.mark.parametrize("rule, forwards", [("kept", 2), ("bare", 4)])
def test_a_recomputed_layer_runs_the_flash_kernel_once(stack, monkeypatch,
                                                       rule, forwards):
    loss, x = stack
    if rule == "bare":
        monkeypatch.setattr(recompute, "policy", None)
    assert _kernels(jax.make_jaxpr(jax.grad(loss))(x).jaxpr) == {
        "mx_flash_fwd": forwards, "mx_flash_dq": 2, "mx_flash_dkv": 2}


def _inside(residuals):
    """(shape, where from) of what a layer kept that is no weight, no
    argument and no layer's result."""
    return [(aval.shape, src) for aval, src in residuals
            if "constant" not in src and "argument" not in src
            and "elemwise.py" not in src and __file__ not in src]


def test_the_kernels_output_and_row_sums_stay_its_operands_do_not(stack):
    loss, x = stack
    kept = _inside(saved_residuals(loss, x))
    of_kernel = sorted(shape for shape, src in kept
                       if "flash_attention.py" in src)
    assert of_kernel == [(B * HEADS, L), (B * HEADS, L)] + \
        [(B * HEADS, L, D // HEADS)] * 2                   # lse and out
    # q, k and v have out's shape: no other tensor of it stays
    assert sum(shape == (B * HEADS, L, D // HEADS) for shape, _ in kept) == 2
    assert any("mx_kernel_out" in src for _, src in kept)


def test_a_product_with_a_weight_stays_a_norms_result_does_not(stack):
    loss, x = stack
    kept = _inside(saved_residuals(loss, x))
    products = [shape for shape, src in kept if "fully_connected" in src]
    assert products == [(B, L, D)] * 6            # q, k, v of two layers
    assert not any("rms_norm" in src for _, src in kept)
    plain = _inside(saved_residuals(
        lambda x: loss(x, wrap=lambda layer, h: layer(h)), x))
    assert any("rms_norm" in src for _, src in plain)
    # nothing but the kernel's two results and the products
    assert len(kept) == 2 * (2 + 3)


def test_the_rule_counts_what_it_marks_an_upper_bound_of_what_stays(stack):
    loss, x = stack
    with recompute.tally() as kept:
        jax.make_jaxpr(jax.grad(loss))(x)
    out, lse = B * HEADS * L * (D // HEADS) * F32, B * HEADS * L * F32
    product = B * L * D * F32
    stays = sum(np.prod(shape) * F32
                for shape, _ in _inside(saved_residuals(loss, x)))
    assert stays == 2 * (out + lse + 3 * product)
    # the output product is marked too; no backward operation reads it (it
    # only feeds the residual add) and jax drops it
    assert (kept.layers, kept.tensors) == (2, 2 * (2 + 4))
    assert kept.bytes == stays + 2 * product
    jax.make_jaxpr(jax.grad(loss))(x)             # no open tally: no count
    assert kept.tensors == 12


def test_outside_a_step_the_call_is_plain_and_nothing_is_counted(stack):
    layer = _Layer("plain_")
    layer.initialize(mx.init.Normal(0.3), ctx=mx.cpu())
    x = nd.array(np.ones((B, L, D), np.float32))
    with recompute.tally() as kept:
        got = checkpointed(layer, x)
    np.testing.assert_array_equal(got.asnumpy(), layer(x).asnumpy())
    assert kept == recompute.Kept()


def test_a_traced_gradient_reports_what_its_layers_keep_once(tmp_path):
    from mxnet_tpu import gluon
    from mxnet_tpu.models.nemotron_h import nemotron_h
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    telemetry.reset()
    telemetry.enable(str(tmp_path))
    try:
        mx.random.seed(11)
        net = nemotron_h(
            vocab_size=48, hidden_size=16, hybrid_override_pattern="ME*",
            mamba_num_heads=2, mamba_head_dim=8, ssm_state_size=8,
            n_groups=1, chunk_size=8, num_attention_heads=4,
            num_key_value_heads=2, head_dim=4, n_routed_experts=8,
            experts_held=(2, 4), num_experts_per_tok=2,
            moe_intermediate_size=8, moe_shared_expert_intermediate_size=12)
        net.initialize(mx.init.Normal(0.1), ctx=mx.cpu())
        ce = gluon.loss.SoftmaxCrossEntropyLoss()
        step = DataParallelStep(
            net,
            lambda lg, lb: ce(lg.reshape(-1, lg.shape[-1]), lb.reshape(-1)),
            mesh=local_mesh(devices=jax.devices()[:1]), optimizer="adam",
            optimizer_params={"learning_rate": 1e-2})
        tokens = np.random.default_rng(0).integers(0, 48, (2, 12),
                                                   dtype=np.int32)
        for _ in range(2):
            step.step(nd.array(tokens, dtype="int32"),
                      nd.array(tokens.astype(np.float32)))
        summary = telemetry.summary()
    finally:
        telemetry.disable()
        telemetry.reset()
    kept = summary["recompute_kept"]
    assert kept["layers"] == 3 and kept["tensors"] > 3
    # the scan layer's in_proj alone: 2 x 12 rows of 2*16 + 2*8 + 2 floats
    assert kept["bytes"] > 2 * 12 * 50 * F32
    assert summary["events"]["recompute_kept"] == 1      # not once a step
    assert not telemetry.summary()["recompute_kept"]
