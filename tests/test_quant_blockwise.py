"""Block quantization primitives (ISSUE 15): wire-format round trips,
the documented error model, the zero/denormal guard, bit-exact replay,
and the Pallas-interpret vs XLA-composite parity."""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.quant import (block_dequantize, block_quantize,
                              predicted_rms_error, quant_block,
                              quant_enabled, quantization_error)
from paddle_tpu.quant.blockwise import padded_size


def _roundtrip(x, block=None):
    q, s = block_quantize(jnp.asarray(x), block=block)
    back = block_dequantize(q, s, size=np.asarray(x).size)
    return np.asarray(q), np.asarray(s), np.asarray(back)


class TestWireFormat:
    def test_shapes_and_dtypes(self):
        rng = np.random.RandomState(0)
        x = rng.randn(1000).astype("float32")  # odd tail: 1000 % 256 != 0
        q, s, back = _roundtrip(x, block=256)
        assert q.dtype == np.int8
        assert s.dtype == np.float32
        assert q.size == padded_size(1000, 256) == 1024
        assert s.size == 4
        assert back.size == 1000

    def test_odd_tail_blocks_round_trip(self):
        """The zero-padded tail must not disturb the real elements: pad
        quantizes to 0 under the tail block's scale, dequant + trim is
        exact about which elements exist."""
        rng = np.random.RandomState(1)
        for numel in (1000, 257, 255, 129):
            x = rng.randn(numel).astype("float32")
            q, s, back = _roundtrip(x, block=256)
            step = s.max()
            assert np.max(np.abs(back - x)) <= step / 2 + 1e-7
            # pad region of the int8 payload is exactly zero
            assert not q[numel:].any()

    def test_single_element_bucket(self):
        q, s, back = _roundtrip(np.array([3.25], "float32"), block=256)
        # one element is its own absmax: round trips exactly
        assert back[0] == np.float32(3.25)
        assert q[0] == 127

    def test_f32_vs_bf16_inputs(self):
        """bf16 input quantizes through the same f32 math and dequants
        back in the requested dtype."""
        rng = np.random.RandomState(2)
        xf = rng.randn(512).astype("float32")
        xb = jnp.asarray(xf).astype(jnp.bfloat16)
        q, s = block_quantize(xb, block=256)
        back = block_dequantize(q, s, size=512, dtype=jnp.bfloat16)
        assert back.dtype == jnp.bfloat16
        err = np.abs(np.asarray(back, "float32")
                     - np.asarray(xb, "float32"))
        assert err.max() <= np.asarray(s).max()  # step + bf16 rounding

    def test_shape_reshape(self):
        rng = np.random.RandomState(3)
        x = rng.randn(12, 33).astype("float32")
        q, s = block_quantize(jnp.asarray(x))
        back = block_dequantize(q, s, shape=(12, 33))
        assert back.shape == (12, 33)


class TestZeroAndDenormal:
    def test_zero_input_no_nan(self):
        q, s, back = _roundtrip(np.zeros(512, "float32"))
        assert not q.any()
        assert np.isfinite(s).all()
        assert s.min() > 0  # the unit-scale guard
        assert not back.any()

    def test_zero_block_among_live_blocks(self):
        x = np.zeros(512, "float32")
        x[256:] = np.linspace(-1, 1, 256)
        q, s, back = _roundtrip(x, block=256)
        assert np.isfinite(back).all()
        assert not back[:256].any()

    def test_denormal_input_no_nan(self):
        x = np.full(256, 1e-41, "float32")  # subnormal f32
        q, s, back = _roundtrip(x, block=256)
        assert np.isfinite(back).all()
        assert np.isfinite(s).all()


class TestErrorModel:
    def test_max_abs_error_bound(self):
        """Documented bound: per-element abs error <= m/254 (half the
        quantization step) within each block."""
        rng = np.random.RandomState(4)
        x = rng.randn(2048).astype("float32")
        q, s, back = _roundtrip(x, block=256)
        err = np.abs(back - x).reshape(-1, 256)
        bound = (s / 2.0)[:, None]  # s = m/127, so s/2 = m/254
        assert (err <= bound + 1e-7).all()

    def test_measured_rms_tracks_model(self):
        rng = np.random.RandomState(5)
        d = quantization_error(rng.randn(4096).astype("float32"))
        measured = float(d["measured_rms"])
        predicted = float(d["predicted_rms"])
        assert predicted > 0
        # dense gaussian data is the model's home regime
        assert 0.5 <= measured / predicted <= 2.0
        assert float(d["rel_error"]) < 0.02  # ~0.4% typical for randn

    def test_zero_input_rel_error_zero(self):
        d = quantization_error(np.zeros(512, "float32"))
        assert float(d["rel_error"]) == 0.0
        assert float(d["measured_rms"]) == 0.0

    def test_predicted_rms_formula(self):
        s = np.array([0.5, 0.1], "float32")
        expect = np.sqrt(np.mean(s ** 2) / 12.0)
        assert np.isclose(float(predicted_rms_error(s)), expect)


class TestKnobsAndReplay:
    def test_block_env_override(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_QUANT_BLOCK", "128")
        assert quant_block() == 128
        q, s = block_quantize(jnp.zeros(200))
        assert np.asarray(s).size == padded_size(200, 128) // 128
        monkeypatch.setenv("PADDLE_TPU_QUANT_BLOCK", "not-a-number")
        assert quant_block() == 256

    def test_kill_switch_flag(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_QUANT", raising=False)
        assert quant_enabled()
        monkeypatch.setenv("PADDLE_TPU_QUANT", "0")
        assert not quant_enabled()

    def test_bit_exact_replay(self):
        """Quantization is a pure function of the input bits: the same
        tensor quantizes to identical bits every time (forward-only op,
        no saved state, exact replay)."""
        rng = np.random.RandomState(6)
        x = jnp.asarray(rng.randn(1024).astype("float32"))
        q1, s1 = block_quantize(x)
        q2, s2 = block_quantize(x)
        assert np.array_equal(np.asarray(q1), np.asarray(q2))
        assert np.array_equal(np.asarray(s1), np.asarray(s2))


class TestPallasParity:
    def test_interpret_matches_xla_composite(self, monkeypatch):
        """PADDLE_TPU_PALLAS=interpret drives the fused kernel through
        the Pallas interpreter on CPU; its bits must match the XLA
        composite fallback (the autotune ``quant`` family swaps grid
        shapes, never values)."""
        from paddle_tpu.ops.pallas import use_pallas

        rng = np.random.RandomState(7)
        # eligible shape: block % 128 == 0, nblocks % 8 == 0
        x = jnp.asarray(rng.randn(8 * 256).astype("float32"))
        monkeypatch.setenv("PADDLE_TPU_PALLAS", "off")
        q_x, s_x = block_quantize(x, block=256)
        back_x = block_dequantize(q_x, s_x)
        monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
        assert use_pallas() == (True, True)
        q_p, s_p = block_quantize(x, block=256)
        back_p = block_dequantize(q_p, s_p)
        assert np.array_equal(np.asarray(q_x), np.asarray(q_p))
        assert np.array_equal(np.asarray(s_x), np.asarray(s_p))
        assert np.array_equal(np.asarray(back_x), np.asarray(back_p))

    def test_ineligible_shape_falls_back(self, monkeypatch):
        """Shapes off the kernel's grid (odd block counts) run the XLA
        composite even in interpret mode — and still round trip."""
        monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
        rng = np.random.RandomState(8)
        x = rng.randn(3 * 256).astype("float32")  # nblocks=3, not %8
        q, s, back = _roundtrip(x, block=256)
        assert np.max(np.abs(back - x)) <= np.asarray(s).max() / 2 + 1e-7
