"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU): shape &
dtype sweeps, masking modes, gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.serving import quant

KEY = jax.random.key(7)


def rand(key_i, shape, dtype=jnp.float32, scale=1.0):
    x = jax.random.normal(jax.random.fold_in(KEY, key_i), shape,
                          jnp.float32) * scale
    return x.astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-3, atol=2e-3),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}

# acceptance bound for the paged-attention kernel (f32 serving shapes)
PAGED_TOL_F32 = dict(rtol=1e-5, atol=1e-5)

# quantization tolerance tiers (docs/kernels.md "Quantized paged KV"):
# drift of a quantized pool's attention output vs the fp32-pool oracle.
# Kernel-vs-ref parity on the SAME quantized inputs stays at the f32
# bound — both sides dequantize identical codes, so the only error is
# the same online-softmax reassociation fp32 already tolerates.
# Measured drift on N(0,1) pools: int8 ~1e-2 (7.9-bit mantissa at
# per-(token, head) absmax scaling), fp8_e4m3 ~7e-2 (3-bit mantissa).
KV_TIERS = {"fp32": PAGED_TOL_F32,
            "int8": dict(rtol=5e-2, atol=5e-2),
            "fp8_e4m3": dict(rtol=1.5e-1, atol=1.5e-1)}


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("window", [None, 64])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_masks_and_dtypes(self, causal, window, dtype):
        q = rand(1, (2, 4, 256, 128), dtype)
        k = rand(2, (2, 2, 256, 128), dtype)
        v = rand(3, (2, 2, 256, 128), dtype)
        out = ops.flash_attention(q, k, v, causal, None, window)
        exp = ref.flash_attention(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            **TOL[dtype])

    @pytest.mark.parametrize("shape", [
        (1, 1, 128, 64),     # MQA small head
        (2, 8, 384, 128),    # non-pow2 seq (block remainder)
        (1, 4, 256, 96),     # pad path (d % 128 != 0, MLA-like)
        (1, 4, 512, 256),    # gemma head_dim 256
    ])
    def test_shape_sweep(self, shape):
        b, h, s, d = shape
        hkv = max(1, h // 2)
        q = rand(4, (b, h, s, d))
        k = rand(5, (b, hkv, s, d))
        v = rand(6, (b, hkv, s, d))
        out = ops.flash_attention(q, k, v, True, None, None)
        exp = ref.flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=2e-3, atol=2e-3)

    def test_custom_scale(self):
        q = rand(7, (1, 2, 128, 128))
        k = rand(8, (1, 2, 128, 128))
        v = rand(9, (1, 2, 128, 128))
        out = ops.flash_attention(q, k, v, True, 0.05, None)
        exp = ref.flash_attention(q, k, v, causal=True, scale=0.05)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=2e-3, atol=2e-3)

    def test_gradients_match_ref(self):
        q = rand(10, (1, 4, 128, 64))
        k = rand(11, (1, 2, 128, 64))
        v = rand(12, (1, 2, 128, 64))
        g1 = jax.grad(lambda a, b, c: ops.flash_attention(
            a, b, c, True, None, None).sum(), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(lambda a, b, c: ref.flash_attention(
            a, b, c, causal=True).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)

    def test_jit_compatible(self):
        q = rand(13, (1, 2, 128, 128))
        k = rand(14, (1, 2, 128, 128))
        v = rand(15, (1, 2, 128, 128))
        f = jax.jit(lambda a, b, c: ops.flash_attention(a, b, c, True,
                                                        None, None))
        np.testing.assert_allclose(
            np.asarray(f(q, k, v)),
            np.asarray(ref.flash_attention(q, k, v, causal=True)),
            rtol=2e-3, atol=2e-3)


class TestDecodeAttention:
    @pytest.mark.parametrize("window", [None, 128])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_ragged_lengths(self, window, dtype):
        B, Hq, Hkv, Smax, D = 3, 8, 2, 512, 128
        q = rand(20, (B, Hq, 1, D), dtype)
        kc = rand(21, (B, Hkv, Smax, D), dtype)
        vc = rand(22, (B, Hkv, Smax, D), dtype)
        lens = jnp.array([500, 512, 130], jnp.int32)
        out = ops.decode_attention(q, kc, vc, lens, window=window)
        exp = ref.decode_attention(q, kc, vc, lens, window=window)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32),
                                   **TOL[dtype])

    def test_scalar_len_broadcast(self):
        q = rand(23, (2, 4, 1, 64))
        kc = rand(24, (2, 4, 256, 64))
        vc = rand(25, (2, 4, 256, 64))
        out = ops.decode_attention(q, kc, vc, 77)
        exp = ref.decode_attention(q, kc, vc, 77)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=2e-3, atol=2e-3)

    def test_mqa_group(self):
        q = rand(26, (1, 8, 1, 128))
        kc = rand(27, (1, 1, 256, 128))
        vc = rand(28, (1, 1, 256, 128))
        out = ops.decode_attention(q, kc, vc, jnp.array([200]))
        exp = ref.decode_attention(q, kc, vc, jnp.array([200]))
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=2e-3, atol=2e-3)


class TestPagedAttention:
    """Block-table-prefetching kernel vs the gather-then-attend oracle."""

    def _tables(self, s, p, n_pages, key_i):
        """Random DISTINCT physical page ids per slot (p pages each)."""
        perm = jax.random.permutation(jax.random.fold_in(KEY, key_i),
                                      n_pages)[: s * p]
        return perm.reshape(s, p).astype(jnp.int32)

    @pytest.mark.parametrize("window", [None, 6])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_mixed_prefill_decode_batch(self, window, dtype):
        """A flat batch mixing a prefill chunk (slot 0), a fresh prefill
        start (slot 1) and decode tokens (slot 2) + padding."""
        n_pages, ps, hkv, d, hq = 24, 4, 2, 32, 4
        kp = rand(70, (n_pages, ps, hkv, d), dtype)
        vp = rand(71, (n_pages, ps, hkv, d), dtype)
        q = rand(72, (7, hq, d), dtype)
        tables = self._tables(3, 4, n_pages, 73)
        seg = jnp.asarray([0, 0, 1, 2, 2, 2, -1], jnp.int32)
        pos = jnp.asarray([3, 4, 0, 10, 14, 15, 0], jnp.int32)
        out = ops.paged_attention(q, kp, vp, tables, seg, pos,
                                  window=window)
        exp = ref.paged_attention(q, kp, vp, tables, seg, pos,
                                  window=window)
        tol = PAGED_TOL_F32 if dtype == jnp.float32 else TOL[dtype]
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(exp, np.float32), **tol)

    def test_ragged_page_counts(self):
        """Slots with very different live-page counts: table rows are
        0-padded past each sequence's last page and masking must keep
        the padding pages out of the softmax."""
        n_pages, ps, hkv, d, hq = 40, 8, 2, 16, 8
        kp = rand(74, (n_pages, ps, hkv, d))
        vp = rand(75, (n_pages, ps, hkv, d))
        q = rand(76, (4, hq, d))
        tables = np.zeros((4, 4), np.int32)
        tables[0, :1] = [5]                   # 3 tokens: 1 page
        tables[1, :4] = [7, 9, 11, 13]        # 30 tokens: 4 pages
        tables[2, :2] = [2, 3]                # 12 tokens: 2 pages
        tables[3, :1] = [17]
        seg = jnp.asarray([0, 1, 2, 3], jnp.int32)
        pos = jnp.asarray([2, 29, 11, 0], jnp.int32)
        tables = jnp.asarray(tables)
        out = ops.paged_attention(q, kp, vp, tables, seg, pos)
        exp = ref.paged_attention(q, kp, vp, tables, seg, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=1e-5, atol=1e-5)

    def test_shared_prefix_pages(self):
        """Two slots whose tables reference the SAME physical prefix
        pages (prefix-cache dedup) must each attend the shared content
        plus their own divergent tail."""
        n_pages, ps, hkv, d, hq = 16, 4, 2, 16, 4
        kp = rand(77, (n_pages, ps, hkv, d))
        vp = rand(78, (n_pages, ps, hkv, d))
        q = rand(79, (2, hq, d))
        tables = jnp.asarray([[3, 5, 8, 0],    # shared pages 3, 5
                              [3, 5, 9, 0]], jnp.int32)
        seg = jnp.asarray([0, 1], jnp.int32)
        pos = jnp.asarray([10, 11], jnp.int32)
        out = ops.paged_attention(q, kp, vp, tables, seg, pos)
        exp = ref.paged_attention(q, kp, vp, tables, seg, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=1e-5, atol=1e-5)
        # divergent tails -> divergent outputs even at equal positions
        q_same = jnp.stack([q[0], q[0]])
        pos_same = jnp.asarray([11, 11], jnp.int32)
        o = ops.paged_attention(q_same, kp, vp, tables, seg, pos_same)
        assert not np.allclose(np.asarray(o[0]), np.asarray(o[1]))

    def test_matches_gathered_mixed_attention(self):
        """paged_attention over pages == mixed_attention over the
        explicitly gathered per-slot cache (the path it replaced)."""
        n_pages, ps, hkv, d, hq, s, p = 20, 4, 2, 16, 4, 3, 3
        kp = rand(80, (n_pages, ps, hkv, d))
        vp = rand(81, (n_pages, ps, hkv, d))
        q = rand(82, (5, hq, d))
        tables = self._tables(s, p, n_pages, 83)
        seg = jnp.asarray([0, 1, 1, 2, -1], jnp.int32)
        pos = jnp.asarray([4, 7, 8, 11, 0], jnp.int32)
        gidx = (tables[:, :, None] * ps
                + jnp.arange(ps)[None, None, :]).reshape(s, p * ps)
        kc = jnp.take(kp.reshape(n_pages * ps, hkv, d), gidx,
                      axis=0).transpose(0, 2, 1, 3)
        vc = jnp.take(vp.reshape(n_pages * ps, hkv, d), gidx,
                      axis=0).transpose(0, 2, 1, 3)
        out = ops.paged_attention(q, kp, vp, tables, seg, pos)
        exp = ops.mixed_attention(q, kc, vc, seg, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=1e-5, atol=1e-5)

    # ---- quantized KV tier (int8 / fp8_e4m3 codes + per-token scales)

    def _quant_pool(self, kp, vp, kv_dtype):
        """Quantize an fp32 pool into (codes, scales); fp32 passthrough."""
        if kv_dtype == "fp32":
            return kp, vp, None, None
        kc, ksc = quant.quantize(kp, kv_dtype)
        vc, vsc = quant.quantize(vp, kv_dtype)
        return kc, vc, ksc, vsc

    def _check_tier(self, q, kp, vp, tables, seg, pos, kv_dtype,
                    window=None):
        """Two bounds per tier: kernel-vs-ref parity on the SAME
        quantized inputs at the fp32 tolerance (both sides dequantize
        identical codes), and drift vs the fp32-pool oracle at the
        documented tier bound."""
        kc, vc, ksc, vsc = self._quant_pool(kp, vp, kv_dtype)
        out = ops.paged_attention(q, kc, vc, tables, seg, pos,
                                  window=window, k_scale=ksc,
                                  v_scale=vsc)
        exp = ref.paged_attention(q, kc, vc, tables, seg, pos,
                                  window=window, k_scale=ksc,
                                  v_scale=vsc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   **PAGED_TOL_F32)
        oracle = ref.paged_attention(q, kp, vp, tables, seg, pos,
                                     window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                                   **KV_TIERS[kv_dtype])
        return out

    @pytest.mark.parametrize("window", [None, 6])
    @pytest.mark.parametrize("kv_dtype", sorted(KV_TIERS))
    def test_quant_mixed_prefill_decode(self, kv_dtype, window):
        """Quantized pools through the mixed prefill/decode batch."""
        n_pages, ps, hkv, d, hq = 24, 4, 2, 32, 4
        kp = rand(70, (n_pages, ps, hkv, d))
        vp = rand(71, (n_pages, ps, hkv, d))
        q = rand(72, (7, hq, d))
        tables = self._tables(3, 4, n_pages, 73)
        seg = jnp.asarray([0, 0, 1, 2, 2, 2, -1], jnp.int32)
        pos = jnp.asarray([3, 4, 0, 10, 14, 15, 0], jnp.int32)
        self._check_tier(q, kp, vp, tables, seg, pos, kv_dtype,
                         window=window)

    @pytest.mark.parametrize("kv_dtype", sorted(KV_TIERS))
    def test_quant_ragged_page_counts(self, kv_dtype):
        """Padding pages past each sequence's end must stay masked even
        though their (zero) scales dequantize them to exact zeros."""
        n_pages, ps, hkv, d, hq = 40, 8, 2, 16, 8
        kp = rand(74, (n_pages, ps, hkv, d))
        vp = rand(75, (n_pages, ps, hkv, d))
        q = rand(76, (4, hq, d))
        tables = np.zeros((4, 4), np.int32)
        tables[0, :1] = [5]
        tables[1, :4] = [7, 9, 11, 13]
        tables[2, :2] = [2, 3]
        tables[3, :1] = [17]
        seg = jnp.asarray([0, 1, 2, 3], jnp.int32)
        pos = jnp.asarray([2, 29, 11, 0], jnp.int32)
        self._check_tier(q, kp, vp, jnp.asarray(tables), seg, pos,
                         kv_dtype)

    @pytest.mark.parametrize("kv_dtype", sorted(KV_TIERS))
    def test_quant_shared_prefix_pages(self, kv_dtype):
        """Shared physical prefix pages share ONE set of codes+scales;
        both referencing slots must dequantize them identically."""
        n_pages, ps, hkv, d, hq = 16, 4, 2, 16, 4
        kp = rand(77, (n_pages, ps, hkv, d))
        vp = rand(78, (n_pages, ps, hkv, d))
        q = rand(79, (2, hq, d))
        tables = jnp.asarray([[3, 5, 8, 0], [3, 5, 9, 0]], jnp.int32)
        seg = jnp.asarray([0, 1], jnp.int32)
        pos = jnp.asarray([10, 11], jnp.int32)
        out = self._check_tier(q, kp, vp, tables, seg, pos, kv_dtype)
        # divergent tails -> divergent outputs even at equal positions
        kc, vc, ksc, vsc = self._quant_pool(kp, vp, kv_dtype)
        q_same = jnp.stack([q[0], q[0]])
        pos_same = jnp.asarray([11, 11], jnp.int32)
        o = ops.paged_attention(q_same, kc, vc, tables, seg, pos_same,
                                k_scale=ksc, v_scale=vsc)
        assert not np.allclose(np.asarray(o[0]), np.asarray(o[1]))
        assert np.isfinite(np.asarray(out)).all()

    @pytest.mark.parametrize("kv_dtype", sorted(KV_TIERS))
    def test_multi_page_tiles_bitwise(self, kv_dtype):
        """pages_per_tile is a pure grid re-packing: every tile size
        must produce BITWISE-identical outputs (the kernel unrolls the
        same per-page online-softmax updates in the same order)."""
        n_pages, ps, hkv, d, hq = 24, 4, 2, 32, 4
        kp = rand(70, (n_pages, ps, hkv, d))
        vp = rand(71, (n_pages, ps, hkv, d))
        q = rand(72, (7, hq, d))
        tables = self._tables(3, 4, n_pages, 73)
        seg = jnp.asarray([0, 0, 1, 2, 2, 2, -1], jnp.int32)
        pos = jnp.asarray([3, 4, 0, 10, 14, 15, 0], jnp.int32)
        kc, vc, ksc, vsc = self._quant_pool(kp, vp, kv_dtype)
        base = ops.paged_attention(q, kc, vc, tables, seg, pos,
                                   k_scale=ksc, v_scale=vsc,
                                   pages_per_tile=1)
        for ppt in (2, 3, 4, 7):          # 7 > p_pages exercises clamp
            tiled = ops.paged_attention(q, kc, vc, tables, seg, pos,
                                        k_scale=ksc, v_scale=vsc,
                                        pages_per_tile=ppt)
            np.testing.assert_array_equal(np.asarray(base),
                                          np.asarray(tiled))

    def test_multi_page_tiles_with_window(self):
        """Tile packing composes with sliding-window masking."""
        n_pages, ps, hkv, d, hq = 24, 4, 2, 32, 4
        kp = rand(74, (n_pages, ps, hkv, d))
        vp = rand(75, (n_pages, ps, hkv, d))
        q = rand(76, (7, hq, d))
        tables = self._tables(3, 4, n_pages, 77)
        seg = jnp.asarray([0, 0, 1, 2, 2, 2, -1], jnp.int32)
        pos = jnp.asarray([3, 4, 0, 10, 14, 15, 0], jnp.int32)
        exp = ref.paged_attention(q, kp, vp, tables, seg, pos, window=6)
        for ppt in (1, 2, 4):
            out = ops.paged_attention(q, kp, vp, tables, seg, pos,
                                      window=6, pages_per_tile=ppt)
            np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                       **PAGED_TOL_F32)

    def test_default_pages_per_tile_heuristic(self):
        """The auto heuristic packs ~BLOCK_K tokens per tile, clamped
        to the table width and a cap of 8 pages."""
        assert ops.default_pages_per_tile(4, 4) == 4
        assert ops.default_pages_per_tile(8, 64) == 8
        assert ops.default_pages_per_tile(256, 16) == 1
        assert ops.default_pages_per_tile(64, 2) == 2


def _block_layout(name, bq):
    """(seg, pos) of a flat batch laid out as the scheduler lays it:
    each slot's tokens one contiguous run, padding at the tail.  The
    span lengths sit on either side of the query block size ``bq``."""
    spans, pad = {
        "bq-1": ([(0, 0, bq - 1), (1, 9, 1)], 0),
        "bq": ([(0, 0, bq), (1, 9, 1)], 0),
        "bq+1": ([(0, 0, bq + 1), (1, 9, 1)], 0),
        # the benchmark cell's step, scaled down: decodes, then a long
        # prefill chunk deep in its prompt
        "chunk+decodes": ([(s, 20 + 5 * s, 1) for s in range(5)]
                          + [(5, 60 - 2 * bq - 3, 2 * bq + 3)], 0),
        "padding": ([(2, 3, bq + 2), (0, 30, 1)], 5),
        "two-prefills": ([(3, 0, bq + 3), (1, 17, bq - 2), (0, 40, 1)],
                         0),
    }[name]
    seg, pos = [], []
    for slot, start, n in spans:
        seg += [slot] * n
        pos += list(range(start, start + n))
    seg += [-1] * pad
    pos += [0] * pad
    return (jnp.asarray(seg, jnp.int32), jnp.asarray(pos, jnp.int32))


BLOCK_LAYOUTS = ["bq-1", "bq", "bq+1", "chunk+decodes", "padding",
                 "two-prefills"]


class TestPagedQueryBlocks:
    """The paged kernel's query blocks: spans of one sequence share a
    block's page fetches; spans crossing block boundaries, padding and
    several prefills in one step must all match the oracle."""

    # 16 query heads on 2 KV heads: G = 8, so a block holds 16 tokens
    N_PAGES, PS, HKV, D, HQ, S, P = 96, 4, 2, 32, 16, 6, 16

    def _pool(self, key_i, hkv=HKV, hq=HQ, t=1):
        kp = rand(key_i, (self.N_PAGES, self.PS, hkv, self.D))
        vp = rand(key_i + 1, (self.N_PAGES, self.PS, hkv, self.D))
        q = rand(key_i + 2, (t, hq, self.D))
        tables = TestPagedAttention()._tables(self.S, self.P,
                                            self.N_PAGES, key_i + 3)
        return q, kp, vp, tables

    @pytest.mark.parametrize("layout", BLOCK_LAYOUTS)
    @pytest.mark.parametrize("kv_dtype", sorted(KV_TIERS))
    def test_spans_across_block_boundaries(self, layout, kv_dtype):
        bq = ops.query_block_size(self.HQ // self.HKV, 64)
        seg, pos = _block_layout(layout, bq)
        q, kp, vp, tables = self._pool(90, t=seg.shape[0])
        TestPagedAttention()._check_tier(q, kp, vp, tables, seg, pos,
                                       kv_dtype)

    @pytest.mark.parametrize("layout", ["chunk+decodes", "two-prefills"])
    def test_window_6_across_blocks(self, layout):
        bq = ops.query_block_size(self.HQ // self.HKV, 64)
        seg, pos = _block_layout(layout, bq)
        q, kp, vp, tables = self._pool(95, t=seg.shape[0])
        out = ops.paged_attention(q, kp, vp, tables, seg, pos, window=6)
        exp = ref.paged_attention(q, kp, vp, tables, seg, pos, window=6)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   **PAGED_TOL_F32)

    @pytest.mark.parametrize("window", [None, 6])
    def test_mqa_group_of_8(self, window):
        """gemma-2b's grouping: 8 query heads on ONE KV head."""
        bq = ops.query_block_size(8, 64)
        seg, pos = _block_layout("chunk+decodes", bq)
        q, kp, vp, tables = self._pool(100, hkv=1, hq=8, t=seg.shape[0])
        out = ops.paged_attention(q, kp, vp, tables, seg, pos,
                                  window=window)
        exp = ref.paged_attention(q, kp, vp, tables, seg, pos,
                                  window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   **PAGED_TOL_F32)

    def test_block_size_fills_the_mxu_rows(self):
        assert ops.query_block_size(4, 128) == 32    # mistral: G = 4
        assert ops.query_block_size(8, 128) == 16    # gemma-2b: G = 8
        assert ops.query_block_size(4, 8) == 8       # capped at T
        assert ops.query_block_size(256, 128) == 1

    @pytest.mark.parametrize("contiguous", [True, False],
                             ids=["scheduler-layout", "interleaved"])
    @pytest.mark.parametrize("seed", range(3))
    def test_block_descriptors_cover_every_token_once(self, seed,
                                                      contiguous):
        """Every token lands in exactly one block row, a block holds one
        slot's tokens (consecutive ones, in the scheduler's layout), and
        no more than NB blocks are used for any layout."""
        from repro.kernels.decode_attention import query_blocks
        rng = np.random.default_rng(seed)
        n_slots, bq = 16, 8
        for t in (8, 32, 128):
            slots = rng.permutation(n_slots)[:rng.integers(1, n_slots)]
            seg, pos = [], []
            for sl in slots:
                n = int(rng.integers(1, max(2, t // 3)))
                if len(seg) + n > t:
                    break
                start = int(rng.integers(0, 500))
                seg += [int(sl)] * n
                pos += list(range(start, start + n))
            n_live = len(seg)
            seg += [-1] * (t - n_live)
            pos += [0] * (t - n_live)
            seg, pos = np.asarray(seg), np.asarray(pos)
            if not contiguous:
                perm = rng.permutation(t)
                seg, pos = seg[perm], pos[perm]
            b = min(bq, t)
            nb = min(t, -(-t // b) + n_slots + 1)
            rows, row_pos, slot, first, last, tok_row = map(
                np.asarray, query_blocks(jnp.asarray(seg, jnp.int32),
                                         jnp.asarray(pos, jnp.int32),
                                         block_q=b, n_slots=n_slots,
                                         n_blocks=nb))
            used = rows[rows >= 0]
            assert sorted(used.tolist()) == list(range(t))
            assert (rows.reshape(-1)[tok_row] == np.arange(t)).all()
            for i in range(nb):
                r = rows[i][rows[i] >= 0]
                if r.size == 0:
                    assert last[i] == -1
                    continue
                assert len(set(seg[r].tolist())) == 1
                if contiguous:
                    assert (np.diff(r) == 1).all()
                assert slot[i] == max(seg[r[0]], 0)
                assert (first[i], last[i]) == (pos[r].min(), pos[r].max())
                assert (row_pos[i][:r.size] == pos[r]).all()
            # each slot's tokens (and the padding) cut into ceil(n / b)
            expect = sum(-(-int((seg == v).sum()) // b)
                         for v in np.unique(seg))
            assert (rows[:, 0] >= 0).sum() == expect <= nb


class TestRWKV6:
    @pytest.mark.parametrize("shape", [(2, 3, 128, 64), (1, 2, 96, 32),
                                       (1, 1, 64, 128)])
    def test_shapes(self, shape):
        b, h, s, d = shape
        r = rand(30, shape, scale=0.5)
        k = rand(31, shape, scale=0.5)
        v = rand(32, shape, scale=0.5)
        w = jax.nn.sigmoid(rand(33, shape)) * 0.5 + 0.45
        u = rand(34, (h, d), scale=0.1)
        out, st = ops.rwkv6_scan(r, k, v, w, u)
        eo, es = ref.rwkv6_scan(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(out), np.asarray(eo),
                                   rtol=5e-3, atol=5e-3)
        np.testing.assert_allclose(np.asarray(st), np.asarray(es),
                                   rtol=5e-3, atol=5e-3)

    def test_bf16(self):
        shape = (1, 2, 128, 64)
        r = rand(35, shape, jnp.bfloat16, 0.5)
        k = rand(36, shape, jnp.bfloat16, 0.5)
        v = rand(37, shape, jnp.bfloat16, 0.5)
        w = (jax.nn.sigmoid(rand(38, shape)) * 0.5 + 0.45).astype(
            jnp.bfloat16)
        u = rand(39, (2, 64), scale=0.1)
        out, _ = ops.rwkv6_scan(r, k, v, w, u)
        eo, _ = ref.rwkv6_scan(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(eo, np.float32),
                                   rtol=5e-2, atol=5e-2)

    def test_grads(self):
        shape = (1, 2, 64, 32)
        r = rand(40, shape, scale=0.5)
        k = rand(41, shape, scale=0.5)
        v = rand(42, shape, scale=0.5)
        w = jax.nn.sigmoid(rand(43, shape)) * 0.5 + 0.45
        u = rand(44, (2, 32), scale=0.1)
        g1 = jax.grad(lambda a: ops.rwkv6_scan(a, k, v, w, u)[0].sum())(r)
        g2 = jax.grad(lambda a: ref.rwkv6_scan(a, k, v, w, u)[0].sum())(r)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=5e-3, atol=5e-3)


class TestMambaScan:
    @pytest.mark.parametrize("shape", [(2, 96, 256, 16), (1, 64, 512, 16),
                                       (1, 128, 640, 8)])
    def test_shapes(self, shape):
        b, s, di, n = shape
        x = rand(50, (b, s, di), scale=0.5)
        dt = jax.nn.softplus(rand(51, (b, s, di))) * 0.1
        B = rand(52, (b, s, n), scale=0.5)
        C = rand(53, (b, s, n), scale=0.5)
        A = -jnp.exp(rand(54, (di, n)))
        D = jnp.ones((di,))
        out = ops.mamba_scan(x, dt, B, C, A, D)
        exp = ref.mamba_scan(x, dt, B, C, A, D)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=5e-3, atol=5e-3)

    def test_grads(self):
        b, s, di, n = 1, 48, 128, 16
        x = rand(55, (b, s, di), scale=0.5)
        dt = jax.nn.softplus(rand(56, (b, s, di))) * 0.1
        B = rand(57, (b, s, n), scale=0.5)
        C = rand(58, (b, s, n), scale=0.5)
        A = -jnp.exp(rand(59, (di, n)))
        D = jnp.ones((di,))
        g1 = jax.grad(lambda a: ops.mamba_scan(a, dt, B, C, A, D).sum())(x)
        g2 = jax.grad(lambda a: ref.mamba_scan(a, dt, B, C, A, D).sum())(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=5e-3, atol=5e-3)

    def test_state_continuity_vs_chunking(self):
        """Chunked kernel must be exact across chunk boundaries."""
        b, s, di, n = 1, 130, 128, 16   # s straddles chunk=64 boundaries
        x = rand(60, (b, s, di), scale=0.5)
        dt = jax.nn.softplus(rand(61, (b, s, di))) * 0.1
        B = rand(62, (b, s, n), scale=0.5)
        C = rand(63, (b, s, n), scale=0.5)
        A = -jnp.exp(rand(64, (di, n)))
        D = jnp.ones((di,))
        out = ops.mamba_scan(x, dt, B, C, A, D)
        exp = ref.mamba_scan(x, dt, B, C, A, D)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=5e-3, atol=5e-3)
