"""The grouped products' Pallas kernels (``ops/pallas/grouped_matmul.py``)
in the interpreter against ``jax.lax.ragged_dot`` and its pullbacks, the
tile rule at the cells' widths, the expert layer through the kernels
against its XLA twin, and the sites the counter notes.  On the chip the
same comparison is ``chip_smoke.py --probe grouped_dot``;
``tests/test_chip_compile.py`` compiles the kernels at the cells' widths."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.observability import metrics, runtime
from paddle_tpu.ops.pallas import grouped_matmul as gm
from paddle_tpu.parallel import moe

F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture
def kernels(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")


def _operands(rng, m, k, n, g, dtype):
    lhs = jnp.asarray(rng.standard_normal((m, k)), dtype)
    rhs = jnp.asarray(rng.standard_normal((g, k, n)) / np.sqrt(k), dtype)
    ct = jnp.asarray(rng.standard_normal((m, n)), dtype)
    return lhs, rhs, ct


def _ragged(lhs, rhs, ct, rows):
    """ragged_dot's value and pullbacks in float32, what the products do
    not cover set to zero."""
    f32 = [x.astype(F32) for x in (lhs, rhs, ct)]
    with jax.default_matmul_precision("highest"):
        out, pullback = jax.vjp(
            lambda l, r: jax.lax.ragged_dot(l, r, rows), *f32[:2])
        d_lhs, d_rhs = pullback(f32[2])
    covered = (np.arange(lhs.shape[0]) < int(rows.sum()))[:, None]
    return (np.where(covered, out, 0), np.where(covered, d_lhs, 0),
            np.where(np.asarray(rows > 0)[:, None, None], d_rhs, 0))


# rows of each group: uneven, an empty group (first, inside, last), edges
# inside a tile, rows left over past sum(rows), and every row taken
@pytest.mark.parametrize("rows", [
    (37, 0, 120, 9, 50),
    (0, 64, 3, 0, 100),
    (16, 16, 32, 64, 0),
    (80, 80, 80, 16, 0),
    (0, 0, 0, 0, 0),
])
# 7 x 128 and 9 x 128 scaled down by 8 (mellum2's F and D), and 2 of 128
@pytest.mark.parametrize("k,n", [(144, 112), (112, 144), (256, 128)])
def test_three_modes_match_ragged_dot(kernels, rows, k, n):
    rng = np.random.default_rng(sum(rows) + k)
    rows = jnp.asarray(rows, jnp.int32)
    m, g = 256, rows.shape[0]
    lhs, rhs, ct = _operands(rng, m, k, n, g, F32)
    assert gm.tiles(m, k, n, g, F32) is not None       # the kernels run
    want = _ragged(lhs, rhs, ct, rows)
    got = (gm.grouped_matmul(lhs, rhs, rows),
           gm.grouped_matmul_t(ct, rhs, rows),
           gm.grouped_matmul_rhs_grad(lhs, ct, rows))
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == F32
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
    # what no group covers is zero, not what the buffer held
    past = int(rows.sum())
    assert not np.asarray(got[0][past:]).any()
    assert not np.asarray(got[1][past:]).any()
    assert not np.asarray(got[2][np.asarray(rows) == 0]).any()


def test_the_weights_kernel_writes_zero_for_an_empty_group(kernels):
    """The weights' kernel visits a group with no row once, to write its
    zeros (the two other kernels leave the rows past ``sum(rows)`` to the
    entry points, which zero them)."""
    rng = np.random.default_rng(1)
    rows = jnp.asarray([40, 0, 60], jnp.int32)
    lhs, _, ct = _operands(rng, 256, 128, 128, 3, F32)
    raw = np.asarray(gm._tgmm(lhs, ct, rows,
                              gm.tiles(256, 128, 128, 3, F32, "rhs_grad"),
                              True))
    assert not raw[1].any() and raw[0].any() and raw[2].any()


@pytest.mark.parametrize("mode", ["forward", "lhs_grad", "rhs_grad"])
def test_bf16_operands_give_bf16_results_within_their_rounding(kernels,
                                                               mode):
    """bf16 operands, float32 accumulation, bf16 results: within bf16's
    rounding of ragged_dot on the same (bf16) operands in float32."""
    rng = np.random.default_rng(2)
    rows = jnp.asarray([30, 90, 0, 70], jnp.int32)
    lhs, rhs, ct = _operands(rng, 256, 144, 112, 4, BF16)
    got = {"forward": lambda: gm.grouped_matmul(lhs, rhs, rows),
           "lhs_grad": lambda: gm.grouped_matmul_t(ct, rhs, rows),
           "rhs_grad": lambda: gm.grouped_matmul_rhs_grad(lhs, ct, rows)}
    got = got[mode]()
    want = _ragged(lhs, rhs, ct, rows)[["forward", "lhs_grad",
                                        "rhs_grad"].index(mode)]
    assert got.dtype == BF16
    got = np.asarray(got, np.float64)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 4e-3


@pytest.mark.parametrize("cell,m,d,f,g", [
    ("kanana", 18432, 2048, 768, 16),
    ("mellum2", 24576, 2304, 896, 8),
])
def test_the_tile_rule_at_the_cells_widths(cell, m, d, f, g):
    """Every tile divides its dimension, in whole lane tiles or the whole
    width, and a step's blocks fit the budget, in every mode and both
    orientations of the layer's products; at these widths a step holds
    the whole of a group's weight."""
    for k, n in ((d, f), (f, d)):
        for mode in ("forward", "lhs_grad", "rhs_grad"):
            tm, tk, tn = gm.tiles(m, k, n, g, BF16, mode)
            assert m % tm == 0 and k % tk == 0 and n % tn == 0
            assert all(t % 128 == 0 for t in (tm, tk, tn))
            acc = (tk, tn) if mode == "rhs_grad" else (tm, tn)
            assert gm._footprint(tm, tk, tn, 2, acc) <= gm.TILE_BYTES
            assert (tk, tn) == (k, n)
            # a group's share of the block's rows spans several tiles
            assert m // g >= gm.TILES_A_GROUP * tm


def test_a_width_no_tile_fits_routes_to_xla(kernels):
    """A group's weight too wide for any tile (2000 is no multiple of a
    lane tile, and 2000 x 2000 float32 is over the budget): XLA's
    ragged_dot, noted as such."""
    counts = collections.Counter()
    lhs = jnp.ones((64, 2000), F32)
    rows = jnp.asarray([10, 20], jnp.int32)
    assert gm.tiles(64, 2000, 2000, 2, F32, "rhs_grad") is None
    with gm.noting_sites(counts):
        out = gm.grouped_matmul_rhs_grad(lhs, lhs, rows)
    assert list(counts) == [("rhs_grad", "xla", "64x2000x2000", None)]
    np.testing.assert_allclose(out[1], np.full((2000, 2000), 20.0))


def _layer(rng, t=256, d=128, f=112, k=2, held=3, e=8):
    x = jnp.asarray(rng.normal(size=(t, d)), F32)
    idx = jnp.asarray(rng.integers(0, e, size=(t, k)), jnp.int32)
    gates = jnp.asarray(rng.uniform(size=(t, k)), F32)
    w = [jnp.asarray(rng.normal(size=s) * 0.2, F32)
         for s in ((held, d, f), (held, d, f), (held, f, d))]
    return x, idx, gates, w


@pytest.mark.parametrize("keep", [False, True])
def test_the_expert_layer_through_the_kernels_matches_its_xla_twin(
        monkeypatch, keep):
    """``held_experts_ffn``'s value and the gradients of x, the gates and
    the three weights, with the grouped products as kernels against
    XLA's ragged_dot, alone and as a recompute region's re-run takes it
    (the first block's products kept: six transposed products, none
    forward); every site takes the kernel."""
    x, idx, gates, w = _layer(np.random.default_rng(3))

    def run(mode):
        monkeypatch.setenv("PADDLE_TPU_PALLAS", mode)
        kept = None
        if keep:
            _, kept = moe.held_experts_ffn(x, idx, gates, *w, first=2,
                                           total=8, return_residuals=True)

        def loss(x, gates, *w):
            out, _ = moe.held_experts_ffn(x, idx, gates, *w, first=2,
                                          total=8, residuals=kept)
            return jnp.sum(out.astype(F32) ** 2), out
        counts = collections.Counter()
        with gm.noting_sites(counts):
            (_, out), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(x, gates, *w)
        return out, grads, counts

    out, grads, counts = run("interpret")
    want_out, want_grads, xla_counts = run("off")
    np.testing.assert_allclose(out, want_out, atol=1e-4, rtol=1e-4)
    for a, b in zip(grads, want_grads):
        # float32 sums of a thousand rows in another order: to 1e-5 of
        # the largest element
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * np.abs(b).max())
    assert {key[1] for key in counts} == {"kernel"}
    assert {key[1] for key in xla_counts} == {"xla"}
    modes = collections.Counter()
    for (mode, _, _, _), n in counts.items():
        modes[mode] += n
    # the backward: per block two lhs and one weight cotangent for each of
    # gate and up, one each for down
    assert modes["lhs_grad"] == modes["rhs_grad"] == 3 * 2
    # the forward: block 0 (its down product alone where kept) and the
    # loop's body; the backward's body computes its gate and up products,
    # and block 0's too where nothing was kept
    assert modes["forward"] == (1 if keep else 3) + 3 + 2 + (0 if keep else 2)


class _Phase:
    def __init__(self):
        self.attrs = {}

    def set_attr(self, key, value):
        self.attrs[key] = value


def test_the_counter_and_the_compile_phase_say_which_path(kernels):
    """``record_grouped_dot_sites``: the compile phase's attributes and
    ``grouped_dot_sites_total{mode, path}``."""
    x, idx, gates, w = _layer(np.random.default_rng(4))
    counts = collections.Counter()
    with gm.noting_sites(counts):
        jax.grad(lambda x: jnp.sum(moe.held_experts_ffn(
            x, idx, gates, *w, total=8)[0] ** 2))(x)
    metrics.registry().reset()
    phase = _Phase()
    runtime.record_grouped_dot_sites(counts, phase)
    sites = {tuple(sorted(dict(m.labels).items())): m.value
             for m in metrics.registry().collect()
             if m.name == "grouped_dot_sites_total"}
    metrics.registry().reset()
    assert sites == {(("mode", mode), ("path", "kernel")): n
                     for mode, n in (("forward", 10), ("lhs_grad", 6),
                                     ("rhs_grad", 6))}
    assert phase.attrs["grouped_dot_sites_forward_kernel"] == 10
    assert not [a for a in phase.attrs if a.endswith("_xla")]
    tm, tk, tn = gm.tiles(1024, 128, 112, 3, F32)
    assert "forward 1024x128x112: %dx%dx%d" % (tm, tk, tn) \
        in phase.attrs["grouped_dot_tiles"]
