"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

The TPU compiler is installed wherever libtpu is, and compiles for a chip
that is described rather than attached, so these cases catch what the
interpret-mode tests cannot: block shapes the Mosaic lowering refuses,
unsupported vector layouts, programs that do not fit the device.  Nothing
runs and nothing is timed.

Widths are the real ones: conv3d at every layer of ``calo3dgan.config()``
(51x51x25, G 64/32/16/8, D 16/32/64/128, batch 128 per replica, bf16
activations with f32 params as the bf16 policy runs them), flash
attention at qwen2-1.5b (12 heads over 2 KV heads of 128, 2048 tokens)
and the SSD scan at zamba2-1.2b (64 heads of 64, state 64, 2048 tokens).

A case the compiler still refuses is a strict xfail whose reason is the
compiler's own message: the test passes only while that exact refusal
happens, so the change that makes a kernel compile must flip its case
(and may then turn the family's launcher routing default on).

The topology is described inside a fixture, never at import: only one
process at a time may load libtpu, and every xdist worker imports this
file.  The persistent compilation cache is off around these compiles,
since an entry written for a described chip cannot be read back here.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

GATHER = "Only 2D gather is supported"
SHAPE_CAST = "infer-vector-layout: unsupported shape cast"
BLOCK_SHAPE = ("last two dimensions of your block shape are divisible by 8 "
               "and 128 respectively")
V5E_HBM_BYTES = 16 * 1024**3


class Refused(Exception):
    """The compiler refused the program with the expected message."""


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            described = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no libtpu, or another process holds it
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield described
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        cc.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, args, refusal):
    """Compile ``fn`` for the described chip; a refusal whose message
    holds ``refusal`` becomes :class:`Refused`, anything else propagates
    (and fails the case, xfail or not)."""
    try:
        compiled = jax.jit(fn).lower(*args).compile()
    except Exception as e:
        if refusal and refusal in str(e):
            raise Refused(refusal) from e
        raise
    mem = compiled.memory_analysis()
    used = mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        + mem.output_size_in_bytes
    assert used < V5E_HBM_BYTES, f"{used / 1e9:.2f} GB does not fit a v5e"
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _refused(message, cut=""):
    return pytest.mark.xfail(strict=True, raises=Refused,
                             reason=f"v5e compiler: {message}{cut}")


# ---------------------------------------------------------------------------
# conv3d: every 3DGAN layer at calo3dgan.config() widths
# ---------------------------------------------------------------------------

B = 128
# name: (input NDHWC, c_in, c_out, stride, transposed, epilogue activation)
GAN_LAYERS = {
    "gen_up0": ((B, 7, 7, 4, 64), 64, 32, 2, True, "none"),
    "gen_up1": ((B, 14, 14, 8, 32), 32, 16, 2, True, "none"),
    "gen_up2": ((B, 28, 28, 16, 16), 16, 8, 2, True, "none"),
    # batch cut to 8 (see GEN_OUT_CUT)
    "gen_out": ((8, 51, 51, 25, 8), 8, 1, 1, False, "softplus"),
    "disc_conv0": ((B, 51, 51, 25, 1), 1, 16, 2, False, "none"),
    "disc_conv1": ((B, 26, 26, 13, 16), 16, 32, 2, False, "none"),
    "disc_conv2": ((B, 13, 13, 7, 32), 32, 64, 2, False, "none"),
    "disc_conv3": ((B, 7, 7, 4, 64), 64, 128, 2, False, "none"),
}
CONV_REFUSED = {
    ("gen_up0", "bwd"): GATHER, ("gen_up1", "bwd"): GATHER,
    ("gen_up2", "bwd"): GATHER,
    ("gen_out", "fwd"): SHAPE_CAST, ("gen_out", "bwd"): SHAPE_CAST,
    **{(f"disc_conv{i}", d): GATHER for i in range(4)
       for d in ("fwd", "bwd")},
}
# the refusal is the same at batch 128, but there the compiler works for
# ~40 s before giving it
GEN_OUT_CUT = ("; compiled at batch 8, not the model's 128: restore "
               "batch 128 in GAN_LAYERS when this case compiles")
CONV_CASES = [
    pytest.param(layer, d, id=f"{layer}-{d}",
                 marks=([_refused(CONV_REFUSED[layer, d],
                                  GEN_OUT_CUT if layer == "gen_out" else "")]
                        if (layer, d) in CONV_REFUSED else []))
    for layer in GAN_LAYERS for d in ("fwd", "bwd")]


@pytest.mark.parametrize("layer,direction", CONV_CASES)
def test_conv3d_gan_layer_compiles(one_chip, layer, direction):
    from repro.kernels.conv3d import conv3d_bias_act, conv3d_transpose_bias_act
    x_shape, ci, co, stride, transposed, act = GAN_LAYERS[layer]
    op = conv3d_transpose_bias_act if transposed else conv3d_bias_act

    def fwd(x, w, b):
        return op(x, w, b, stride, act, 0.2, False)

    def bwd(x, w, b):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(x, w, b)

    args = (jax.ShapeDtypeStruct(x_shape, jnp.bfloat16, sharding=one_chip),
            jax.ShapeDtypeStruct((3, 3, 3, ci, co), jnp.float32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((co,), jnp.float32, sharding=one_chip))
    _compile(fwd if direction == "fwd" else bwd, args,
             CONV_REFUSED.get((layer, direction)))


# ---------------------------------------------------------------------------
# flash attention at qwen2-1.5b widths
# ---------------------------------------------------------------------------

H, KV, D, S = 12, 2, 128, 2048


def _attn_fwd(q, k, v):
    from repro.kernels.flash_attention import flash_attention
    return flash_attention(q, k, v, True, 0, False)


def _attn_bwd(q, k, v):
    return jax.grad(lambda *a: _attn_fwd(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))(q, k, v)


def _attn_chunk(q, k, v, q_offset, kv_len):
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention_chunk)
    return flash_attention_chunk(q, k, v, q_offset, kv_len, interpret=False)


def _attn_decode(q, k, v, kv_len):
    from repro.kernels.flash_attention.decode import flash_decode
    return flash_decode(q, k, v, kv_len, interpret=False)


# name: (fn, [(shape, dtype)] of its arguments)
ATTN_CASES = {
    "fwd": (_attn_fwd, [((1, S, H, D), jnp.bfloat16),
                        ((1, S, KV, D), jnp.bfloat16),
                        ((1, S, KV, D), jnp.bfloat16)]),
    "bwd": (_attn_bwd, [((1, S, H, D), jnp.bfloat16),
                        ((1, S, KV, D), jnp.bfloat16),
                        ((1, S, KV, D), jnp.bfloat16)]),
    "chunk": (_attn_chunk, [((4, 256, H, D), jnp.bfloat16),
                            ((4, S, KV, D), jnp.bfloat16),
                            ((4, S, KV, D), jnp.bfloat16),
                            ((4,), jnp.int32), ((4,), jnp.int32)]),
    "decode": (_attn_decode, [((8, 1, H, D), jnp.bfloat16),
                              ((8, S, KV, D), jnp.bfloat16),
                              ((8, S, KV, D), jnp.bfloat16),
                              ((8,), jnp.int32)]),
}


@pytest.mark.parametrize("case", [
    pytest.param(c, marks=_refused(BLOCK_SHAPE)) for c in ATTN_CASES])
def test_flash_attention_qwen2_compiles(one_chip, case):
    fn, specs = ATTN_CASES[case]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in specs]
    _compile(fn, args, BLOCK_SHAPE)


# ---------------------------------------------------------------------------
# SSD scan at zamba2-1.2b widths
# ---------------------------------------------------------------------------


def _ssm_fwd(x, b, c, dt, a):
    from repro.kernels.ssm_scan import ssm_scan
    return ssm_scan(x, b, c, dt, a, None, False)


def _ssm_bwd(x, b, c, dt, a):
    return jax.grad(lambda *t: _ssm_fwd(*t).sum(),
                    argnums=(0, 1, 2, 3, 4))(x, b, c, dt, a)


@pytest.mark.parametrize("direction", [
    pytest.param(d, marks=_refused(BLOCK_SHAPE)) for d in ("fwd", "bwd")])
def test_ssm_scan_zamba2_compiles(one_chip, direction):
    heads, head_dim, state = 64, 64, 64
    shapes = [(1, S, heads, head_dim), (1, S, state), (1, S, state),
              (1, S, heads), (heads,)]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    _compile(_ssm_fwd if direction == "fwd" else _ssm_bwd, args, BLOCK_SHAPE)


# the benchmark's moonlight-train-8k-ep8-1chip step: 5 layers of
# moonlight-16b-a3b at published widths, 8 of 64 experts held, a 20480-id
# vocabulary slice, 2 sequences of 8192 tokens, bf16 with f32 masters and
# AdamW, through the engine's custom loop
LM_CELL_PEAK_LIMIT = 15e9


def test_moonlight_ep8_train_step_fits_one_v5e(topo):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import moonlight_16b_a3b as ml
    from repro.launch.mesh import make_mesh
    from repro.models import api
    from repro.optim import optimizers as opt_lib
    from repro.substrate.precision import get_policy
    from repro.train import engine as engine_lib

    cfg = ml.config(n_layers=5, n_held=8, vocab=20480)
    task = engine_lib.lm_task(api.get_model(cfg), cfg, opt_lib.adamw(ml.LR),
                              policy=get_policy("bf16"))
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    eng = engine_lib.Engine(mesh, "custom", dp_axes=("data", "model"))
    rep = NamedSharding(mesh, P())

    def placed(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep)

    batch = {"tokens": placed(jax.ShapeDtypeStruct((2, 8192), jnp.int32))}
    state = jax.tree.map(placed, jax.eval_shape(
        lambda: task.init(jax.random.key(0))))
    key = placed(jax.eval_shape(lambda: jax.random.key(0)))
    compiled = eng.compile_step(task, batch).lower(state, batch, key) \
        .compile()
    peak = compiled.memory_analysis().peak_memory_in_bytes
    print(f"moonlight-train-8k-ep8-1chip step peak: {peak / 1e9:.3f} GB")
    assert "ragged" in compiled.as_text()       # the grouped expert matmuls
    assert peak < LM_CELL_PEAK_LIMIT, peak
