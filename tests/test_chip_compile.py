"""Every Pallas kernel compiles for a described (not attached) TPU v5e.

The TPU compiler is installed in the CPU sandbox and compiles for a chip
that ``topologies.get_topology_desc`` describes, so what Mosaic refuses
(block shapes off the (8, 128) tiling, dynamic sublane offsets on packed
dtypes, too much VMEM) fails HERE, at no chip time — interpret mode
accepts all of it.  Each case goes through the kernel's public entry
point at a real width, so it also proves the routing SELECTS the kernel
there: the compiled module must contain a ``tpu_custom_call``.  Nothing
runs; a compile that passes is not a chip run (``chip_smoke.py`` is).
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

import paddle_tpu.ops.pallas as pallas
from paddle_tpu.ops.pallas.conv_bn_act import (bn_act_epilogue,
                                               epilogue_eligible)
from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.flash_decode import flash_decode
from paddle_tpu.ops.pallas.fused_ln import fused_dropout_add_ln
from paddle_tpu.ops.pallas.paged_flash_decode import paged_flash_decode
from paddle_tpu.parallel.moe import held_experts_ffn
from paddle_tpu.quant.blockwise import block_dequantize, block_quantize

F32, BF16, I32 = jnp.float32, jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def chip():
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip("cannot describe a v5e here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def as_on_chip(monkeypatch):
    """Steer the one gating predicate to its on-chip answer (the code
    sees the CPU backend here), with the cache off: an executable built
    for a described chip cannot be read back without one."""
    monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    monkeypatch.delenv("PADDLE_TPU_FLASH_DROPOUT_DEBUG", raising=False)
    monkeypatch.setattr(pallas, "device_platform", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernels_in(fn, chip, *structs):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in structs]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return sum(pallas.pallas_kernels_in(text).values())


def _grad_sum(fn, argnums):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(F32)), argnums=argnums)


# -- fused dropout+add+LayerNorm -------------------------------------------

def _ln(rate):
    return lambda x, r, g, b, seed: fused_dropout_add_ln(
        x, r, g, b, dropout_rate=rate, seed=seed)


def _ln_args(n, d, dt):
    return (((n, d), dt), ((n, d), dt), ((d,), F32), ((d,), F32),
            ((1,), I32))


@pytest.mark.parametrize("n,d,dt,rate", [
    (8192, 768, BF16, 0.1),     # BERT-base seq128 bs64 / seq512 bs16
    (8192, 768, F32, 0.0),
    (128, 768, BF16, 0.0),      # serving, batch bucket 1
])
def test_fused_ln_forward(chip, n, d, dt, rate):
    assert _kernels_in(_ln(rate), chip, *_ln_args(n, d, dt)) == 1


def test_fused_ln_backward(chip):
    g = _grad_sum(_ln(0.1), (0, 1, 2, 3))
    assert _kernels_in(g, chip, *_ln_args(8192, 768, BF16)) == 2


def test_fused_ln_routes_around_unaligned_row_blocks(chip):
    """264 rows only split into 8-row blocks, whose (1, 8) statistics
    tile the lowering refuses — routing must pick the composite."""
    assert _kernels_in(_ln(0.0), chip, *_ln_args(264, 768, BF16)) == 0


# -- flash attention ---------------------------------------------------------

def _flash(causal, rate, bias):
    def fn(q, k, v, *rest):
        rest = list(rest)
        b = rest.pop(0) if bias else None
        seed = rest.pop(0) if rate else None
        return flash_attention(q, k, v, bias=b, causal=causal,
                               dropout_rate=rate, dropout_seed=seed)
    return fn


def _flash_args(b, h, t, d, dt, rate, bias, dv=None):
    qkv = [((b, h, t, d), dt)] * 2 + [((b, h, t, dv or d), dt)]
    return qkv + ([((b, t), F32)] if bias else []) \
        + ([((1,), I32)] if rate else [])


@pytest.mark.parametrize("b,h,t,d,causal,rate,bias,grad", [
    (16, 12, 512, 64, False, 0.1, True, False),   # BERT-base seq512 bs16
    (16, 12, 512, 64, False, 0.1, True, True),
    (4, 12, 2048, 64, True, 0.0, False, False),   # long causal
    (4, 12, 2048, 64, True, 0.0, False, True),
])
def test_flash_attention(chip, b, h, t, d, causal, rate, bias, grad):
    fn = _flash(causal, rate, bias)
    if grad:
        fn = _grad_sum(fn, (0, 1, 2))
    n = _kernels_in(fn, chip, *_flash_args(b, h, t, d, BF16, rate, bias))
    assert n == (3 if grad else 1)  # fwd + (dK/dV, dQ)


@pytest.mark.parametrize("grad", [False, True])
def test_flash_attention_at_d_qk_other_than_d_v(chip, grad):
    """Latent attention at the published widths: Q and K 192 wide (128
    position-free, 64 rotary), V and the output 128; causal, 4 x 4096."""
    fn = _flash(True, 0.0, False)
    if grad:
        fn = _grad_sum(fn, (0, 1, 2))
    n = _kernels_in(fn, chip, *_flash_args(4, 32, 4096, 192, BF16, 0.0,
                                           False, dv=128))
    assert n == (3 if grad else 1)


@pytest.mark.parametrize("grad", [False, True])
@pytest.mark.parametrize("window", [1024, None])
def test_flash_attention_with_grouped_heads_and_a_window(chip, window, grad):
    """The grouped-query cell's two sites at the published widths: 32
    query heads on 4 key-value heads of 128, 2 x 8192 (two spans a sweep,
    block indices clamped to the band), a window of 1024 or none."""
    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window)

    if grad:
        fn = _grad_sum(fn, (0, 1, 2))
    n = _kernels_in(fn, chip, ((2, 32, 8192, 128), BF16),
                    ((2, 4, 8192, 128), BF16), ((2, 4, 8192, 128), BF16))
    assert n == (3 if grad else 1)


# -- the dropless expert layer -------------------------------------------------

@pytest.mark.parametrize("grad", [False, True])
def test_expert_layer_loop(chip, grad):
    """The kanana cell's expert layer (16,384 tokens, top 6, 16 of 128
    experts held, widths 2048 / 768, bf16): the loop over blocks holds
    XLA's grouped kernel, and forward + backward keep their temporaries
    under a quarter of the 2.08 GiB that one buffer of every choice
    compiled to (PR 29's form; 1.13 GiB forward)."""
    t, d, f, k, held = 16384, 2048, 768, 6, 16

    def layer(x, idx, gates, *w):
        return held_experts_ffn(x, idx, gates, *w)[0]

    def backward(x, idx, gates, *w):
        return jax.grad(lambda x, gates, *w: jnp.sum(
            layer(x, idx, gates, *w).astype(F32) ** 2),
            argnums=(0, 1, 2, 3, 4))(x, gates, *w)

    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in (
        ((t, d), BF16), ((t, k), I32), ((t, k), F32), ((held, d, f), BF16),
        ((held, d, f), BF16), ((held, f, d), BF16))]
    compiled = jax.jit(backward if grad else layer).lower(*args).compile()
    kernels = pallas.pallas_kernels_in(compiled.as_text())
    assert any("ragged-dot" in name for name in kernels)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.08 * 2**30 / 4


# -- decode kernels (forward only) -------------------------------------------

def test_flash_decode(chip):
    b, h, t, d = 16, 16, 4096, 128
    n = _kernels_in(flash_decode, chip, ((b, h, d), BF16),
                    ((b, h, t, d), BF16), ((b, h, t, d), BF16),
                    ((b,), I32))
    assert n == 1


@pytest.mark.parametrize("dt", [BF16, F32])
def test_paged_flash_decode(chip, dt):
    s, h, d, bl, nblk, mb = 16, 16, 128, 16, 4096, 256
    n = _kernels_in(paged_flash_decode, chip, ((s, h, d), dt),
                    ((nblk, h, bl, d), dt), ((nblk, h, bl, d), dt),
                    ((s,), I32), ((s, mb), I32))
    assert n == 1


# -- conv + batch-norm + activation epilogue ---------------------------------

def _bn_args(r, c):
    return (((r, c), BF16),) + (((c,), F32),) * 4


def _bn_act(y, g, b, m, r):
    assert epilogue_eligible(y.shape[0], y.shape[1], "relu")
    return bn_act_epilogue(y, g, b, m, r, act="relu")


@pytest.mark.parametrize("grad", [False, True])
def test_conv_bn_act(chip, grad):
    fn = _grad_sum(_bn_act, (0, 1, 2, 3, 4)) if grad else _bn_act
    # ResNet-50 stage 1 at batch 128: 128*56*56 rows x 256 channels
    assert _kernels_in(fn, chip, *_bn_args(401408, 256)) == 1


# -- lookup_table: XLA's own gather ------------------------------------------

@pytest.mark.parametrize("rows,dim,dt,ids", [
    (30522, 768, F32, (64, 128, 1)),    # BERT-base word table
    (30522, 768, BF16, (64, 128, 1)),
    (512, 768, F32, (64, 128, 1)),      # position table
    (2, 768, F32, (64, 128, 1)),        # token-type table
    (2, 768, BF16, (64, 128, 1)),
    (1000003, 128, F32, (64, 128, 1)),  # DeepFM-scale table
    (30522, 768, F32, (13,)),           # ids that fill no 8-row tile
])
def test_lookup_table_stays_xlas_gather(chip, rows, dim, dt, ids):
    """A Pallas row-DMA gather took 2.2-5.7x XLA's own at each of these
    tables on the chip (PR 22) and was deleted: the lookup compiles to
    no Mosaic kernel."""
    from paddle_tpu.ops.registry import LoweringContext, get_op_def

    def lookup(w, i):
        return get_op_def("lookup_table").fn(
            LoweringContext(), {"padding_idx": 0}, w, i)

    assert _kernels_in(lookup, chip, ((rows, dim), dt), (ids, I32)) == 0


# -- block quantize / dequantize ---------------------------------------------

@pytest.mark.parametrize("numel,kernels", [
    (768 * 3072, 2),     # one BERT-base FFN weight gradient
    (8 * 264 * 256, 0),  # 2112 blocks: row blocks of 64, off the 128 lanes
])
def test_block_quant_roundtrip(chip, numel, kernels):
    def fn(x):
        q, s = block_quantize(x, block=256)
        return block_dequantize(q, s, size=numel)

    assert _kernels_in(fn, chip, ((numel,), F32)) == kernels
