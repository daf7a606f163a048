"""Kernel autotuner + r14 perf bundle — the fast-tier contract.

Five surfaces, all under the ``tuning`` marker:

1. the registry (``ops/tuning.py``): candidate generation alignment/
   VMEM bounds, store roundtrip by atomic rename, invalidation on
   platform or schema change, stale-entry fallback, and the load-
   bearing acceptance criterion — an EMPTY cache is bit-identical to
   the pre-r14 hand-picked constants;
2. the sweep driver: fallback always candidate 0, winner >= 1.0x by
   construction, unlayoutable candidates skipped (not fatal), winners
   recorded and re-read;
3. the int4/fp8 rungs: nibble/e4m3 codec roundtrip bounds, packed-leaf
   dispatch parity (Pallas interpret vs reference), rung gather/logit
   plumb through the packed ``tok`` table, declared accuracy budgets +
   resident-byte ratios (bench-tune's gate, asserted here directly);
4. the fused int8 conv: patches+fused-matmul vs the in-graph widen at
   ragged shapes, eligibility dispatch (stride/dilation/groups keep the
   widen);
5. the Pallas paged-attention kernel: parity vs the ``decode_pages``
   gather path on the token-major pool (incl. GQA + rope + a
   NaN-poisoned trash page — the full-capacity-neighbor regression
   scenario; to the last place wherever the CPU sums alike), and
   the scheduler's one decode program end to end on both; plus the ``cli
   tune`` smoke artifact and run-report's kernel-tuning section.
"""

import glob
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.ops import quant, tuning

pytestmark = pytest.mark.tuning


@pytest.fixture()
def interpret_mode():
    prev = os.environ.get("BIGDL_TPU_PALLAS_INTERPRET")
    os.environ["BIGDL_TPU_PALLAS_INTERPRET"] = "1"
    yield
    if prev is None:
        os.environ.pop("BIGDL_TPU_PALLAS_INTERPRET", None)
    else:
        os.environ["BIGDL_TPU_PALLAS_INTERPRET"] = prev


@pytest.fixture()
def tune_dir(tmp_path):
    """A fresh, EMPTY store for one test; restores env/default
    resolution after."""
    d = str(tmp_path / "tune")
    tuning.set_tune_dir(d)
    yield d
    tuning.set_tune_dir(None)


# -- 1. registry -------------------------------------------------------------

class TestRegistry:
    def test_candidates_aligned_and_bounded(self):
        for bm, bn, bk in tuning.matmul_candidates(200, 700, 300):
            assert bm % 32 == 0 and bn % 128 == 0 and bk % 128 == 0
            assert (bm * bk * 4 + bn * bk + bn * 4 + 2 * bm * bn * 4
                    <= tuning.VMEM_CAP_BYTES)
        # candidates never exceed the padded problem size
        assert all(bm <= 224 for bm, _, _ in
                   tuning.matmul_candidates(200, 700, 300))
        for (bq, bk) in tuning.attention_stream_candidates(256, 512, 64):
            assert 256 % bq == 0 and 512 % bk == 0
        for (r,) in tuning.elementwise_candidates(100_000):
            assert r % 8 == 0
        for (bc,) in tuning.pool_candidates(96, 28, 28, 4):
            assert 96 % bc == 0

    def test_store_roundtrip_and_merge(self, tune_dir):
        fb = (32, 128, 128)
        assert tuning.lookup("op.a", "m1k1n1", "f32", fb) == fb
        tuning.record("op.a", "m1k1n1", "f32",
                      {"tiles": [64, 128, 256], "speedup": 1.1})
        tuning.record("op.b", "m2k2n2", "f32",
                      {"tiles": [32, 256, 128], "speedup": 1.2})
        assert tuning.lookup("op.a", "m1k1n1", "f32", fb) == (64, 128,
                                                             256)
        assert tuning.lookup("op.b", "m2k2n2", "f32", fb) == (32, 256,
                                                              128)
        e = tuning.lookup_entry("op.a", "m1k1n1", "f32")
        assert e["speedup"] == 1.1
        # one file per platform, schema-versioned
        path = tuning._store_path()
        with open(path) as f:
            data = json.load(f)
        assert data["schema"] == tuning.SCHEMA_VERSION
        assert data["platform"] == tuning.platform()

    def test_stale_platform_and_schema_ignored(self, tune_dir):
        fb = (32, 128, 128)
        path = tuning._store_path()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # wrong platform: the whole file is ignored, never misapplied
        with open(path, "w") as f:
            json.dump({"schema": tuning.SCHEMA_VERSION,
                       "platform": "tpu-v9000",
                       "entries": {tuning.key("op.a", "s", "f32"):
                                   {"tiles": [8, 8, 8]}}}, f)
        tuning.invalidate_cache()
        assert tuning.lookup("op.a", "s", "f32", fb) == fb
        # wrong schema: same posture
        with open(path, "w") as f:
            json.dump({"schema": tuning.SCHEMA_VERSION + 1,
                       "platform": tuning.platform(),
                       "entries": {tuning.key("op.a", "s", "f32"):
                                   {"tiles": [8, 8, 8]}}}, f)
        tuning.invalidate_cache()
        assert tuning.lookup("op.a", "s", "f32", fb) == fb
        # corrupt json: no cache, not an error
        with open(path, "w") as f:
            f.write("{not json")
        tuning.invalidate_cache()
        assert tuning.lookup("op.a", "s", "f32", fb) == fb

    def test_malformed_entry_falls_back(self, tune_dir):
        fb = (32, 128, 128)
        tuning.record("op.a", "s", "f32", {"tiles": "garbage"})
        assert tuning.lookup("op.a", "s", "f32", fb) == fb
        tuning.record("op.a", "s", "f32", {"tiles": [0, -1]})
        assert tuning.lookup("op.a", "s", "f32", fb) == fb

    def test_oversized_entry_falls_back(self, tune_dir):
        """An aligned but VMEM-oversized foreign entry (hand-edited
        store, a sweep run with a larger cap) must fall back at lookup,
        not fail Mosaic's scoped-VMEM limit at compile time."""
        m, k, n = 40, 200, 100
        fb = quant.fallback_matmul_tiles(m, k)
        tuning.record("int8_matmul.w8", tuning.matmul_sig(m, k, n),
                      "float32", {"tiles": [2048, 2048, 4096]})
        assert quant._matmul_tiles("int8_matmul.w8", m, k, n,
                                   "float32") == fb
        from bigdl_tpu.ops import attention as att
        sig = tuning.attention_sig(4096, 4096, 128)
        tuning.record("attention.stream", sig, "float32",
                      {"tiles": [2048, 4096]})
        assert att._tuned_stream_blocks(4096, 4096, 128,
                                        np.dtype("float32")) \
            == att._pick_stream_blocks(4096, 4096)
        # every other family honors the same contract
        from bigdl_tpu.ops import fp16, lrn, pooling
        tuning.record("fp16_codec", tuning.elementwise_sig(99),
                      "u16", {"tiles": [1 << 20]})
        assert fp16._block_rows(99) == fp16._BLOCK_ROWS
        tuning.record("lrn", tuning.lrn_sig(64, 512), "f32",
                      {"tiles": [1 << 20]})
        assert lrn._pick_tile(512, 64) == lrn.fallback_tile(512)
        tuning.record("pool.bc", tuning.pool_sig(512, 28, 28, 4),
                      "i4", {"tiles": [512]})     # divides, over budget
        assert pooling._pick_bc(512, 28, 28, 4) \
            == pooling.fallback_bc(512, 28, 28, 4)
        x = jnp.ones((8, 130), jnp.float32)
        q4 = quant.pack(jnp.ones((100, 130)), mode="w4")
        tuning.record("int4_matmul", tuning.matmul_sig(8, 130, 100),
                      "float32", {"tiles": [4096, 8192]})
        os.environ["BIGDL_TPU_PALLAS_INTERPRET"] = "1"
        try:
            y = quant.int8_matmul(x, q4)      # falls back, not OOM/raise
            assert y.shape == (8, 100)
        finally:
            os.environ.pop("BIGDL_TPU_PALLAS_INTERPRET", None)

    def test_api_overrides_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("BIGDL_TPU_TUNE_DIR", str(tmp_path / "env"))
        assert tuning.tune_dir() == str(tmp_path / "env")
        tuning.set_tune_dir(str(tmp_path / "api"))
        try:
            assert tuning.tune_dir() == str(tmp_path / "api")
        finally:
            tuning.set_tune_dir(None)

    def test_empty_cache_bit_identical(self, tune_dir, interpret_mode):
        """THE acceptance criterion: with an empty store every kernel
        family runs the exact pre-r14 constants — outputs bit-equal to
        the explicitly-pinned fallback tiles."""
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(40, 200), jnp.float32)
        w = jnp.asarray(rng.randn(100, 200), jnp.float32)
        qt = quant.pack(w)
        # the lookup resolves to exactly the hand-picked fallback
        assert quant._matmul_tiles("int8_matmul.w8", 40, 200, 100,
                                   "float32") == (64, 128, 256)
        got = quant.int8_matmul(x, qt)
        pinned = quant._fused_call(quant._w8_kernel, x, qt["q8"],
                                   qt["scale"], x.dtype, jnp.float32,
                                   tiles=(64, 128, 256))
        assert np.array_equal(np.asarray(got), np.asarray(pinned))
        from bigdl_tpu.ops import fp16
        assert fp16._block_rows(12345) == fp16._BLOCK_ROWS
        from bigdl_tpu.ops import attention as att
        f32 = np.dtype("float32")
        assert att._tuned_block_q(256, 256, 64, f32) == \
            att._pick_block_q(256, 256)
        assert att._tuned_stream_blocks(256, 256, 64, f32) == \
            att._pick_stream_blocks(256, 256)

    def test_cached_winner_is_used_and_stale_divisor_rejected(
            self, tune_dir, interpret_mode):
        from bigdl_tpu.ops import attention as att
        f32 = np.dtype("float32")
        sig = tuning.attention_sig(128, 128, 32)
        tuning.record("attention.stream", sig, "float32",
                      {"tiles": [64, 128]})
        assert att._tuned_stream_blocks(128, 128, 32, f32) == (64, 128)
        # a winner that no longer divides the lengths is discarded
        tuning.record("attention.stream", sig, "float32",
                      {"tiles": [48, 128]})
        assert att._tuned_stream_blocks(128, 128, 32, f32) \
            == att._pick_stream_blocks(128, 128)


# -- 2. the sweep driver -----------------------------------------------------

class TestSweep:
    def test_fallback_always_wins_at_worst(self, tune_dir):
        calls = []

        def build(tiles):
            def run():
                calls.append(tiles)
            return run

        e = tuning.sweep("op.x", "s", "f32", (32, 128),
                         [(64, 128), (32, 256)], build, iters=2)
        assert tuple(e["fallback"]) == (32, 128)
        assert calls[0] == (32, 128)          # fallback is candidate 0
        assert e["speedup"] >= 1.0
        assert tuning.lookup("op.x", "s", "f32", (1, 1)) == \
            tuple(e["tiles"])

    def test_broken_candidate_skipped_broken_fallback_fatal(
            self, tune_dir):
        def build(tiles):
            if tiles == (64, 128):
                raise RuntimeError("unlayoutable")
            return lambda: None

        e = tuning.sweep("op.y", "s", "f32", (32, 128),
                         [(64, 128)], build, iters=1)
        assert e["skipped"] == 1 and e["swept"] == 1

        def build2(tiles):
            raise RuntimeError("everything broken")

        with pytest.raises(RuntimeError):
            tuning.sweep("op.z", "s", "f32", (32, 128), [], build2)


# -- 3. int4 / fp8 rungs -----------------------------------------------------

class TestRungs:
    def test_nibble_roundtrip_bounds(self):
        w = jax.random.normal(jax.random.PRNGKey(1), (32, 45))
        q4, s = quant.quantize_nibble(w)
        assert q4.dtype == jnp.int8 and q4.shape == (32, 23)
        back = quant.dequantize_nibble(q4, s, 45)
        err = jnp.max(jnp.abs(back - w))
        assert float(err) <= float(jnp.max(s)) * 0.5 + 1e-6
        # 4D (conv) weights pack along the last axis too
        w4 = jax.random.normal(jax.random.PRNGKey(2), (8, 4, 3, 3))
        q, s = quant.quantize_nibble(w4)
        assert q.shape == (8, 4, 3, 2)
        assert jnp.max(jnp.abs(quant.dequantize_nibble(q, s, 3) - w4)) \
            <= jnp.max(s) * 0.5 + 1e-6

    def test_f8_roundtrip_relative_error(self):
        w = jax.random.normal(jax.random.PRNGKey(3), (64, 80))
        f8, s = quant.quantize_f8(w)
        back = quant.dequantize_f8(f8, s)
        rel = float(jnp.mean(jnp.abs(back - w)) / jnp.mean(jnp.abs(w)))
        assert rel < 0.05                      # e4m3's ~4% grid

    def test_pack_kinds_and_unpack(self):
        w = jax.random.normal(jax.random.PRNGKey(4), (70, 90))
        for mode, kind in (("w8", "q8"), ("w4", "q4"), ("f8", "f8")):
            qt = quant.pack(w, mode=mode)
            assert quant.packed_kind(qt) == kind
            assert quant.is_quantized(qt)
            back = quant.unpack(qt)
            assert back.shape == w.shape
        assert quant.packed_k(quant.pack(w, mode="w4")) == 90
        with pytest.raises(ValueError):
            quant.pack(w, mode="w4", sx=0.1)

    @pytest.mark.parametrize("shape", [(5, 70, 96), (128, 256, 256),
                                       (33, 130, 100)])
    def test_int4_pallas_matches_reference(self, shape, interpret_mode):
        m, k, n = shape
        rng = np.random.RandomState(m)
        x = jnp.asarray(rng.randn(m, k), jnp.float32)
        qt = quant.pack(jnp.asarray(rng.randn(n, k), jnp.float32),
                        mode="w4")
        got = quant.int8_matmul(x, qt)
        want = quant.int4_matmul_reference(x, qt["q4"], qt["scale"], k)
        # same math, different f32 summation order (the kernel reduces
        # the split-half layout): tight allclose, not bit equality
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)

    def test_f8_pallas_matches_reference(self, interpret_mode):
        rng = np.random.RandomState(7)
        x = jnp.asarray(rng.randn(33, 130), jnp.float32)
        qt = quant.pack(jnp.asarray(rng.randn(100, 130), jnp.float32),
                        mode="f8")
        got = quant.int8_matmul(x, qt)
        want = quant.f8_matmul_reference(x, qt["f8"], qt["scale"])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)

    def test_rung_gather_rows(self):
        w = jax.random.normal(jax.random.PRNGKey(5), (50, 64))
        idx = jnp.asarray([0, 7, 49, 7])
        for mode in ("w4", "f8"):
            qt = quant.pack(w, mode=mode)
            rows = quant.int8_gather_rows(qt, idx)
            want = jnp.take(quant.unpack(qt), idx, axis=0)
            assert np.allclose(np.asarray(rows), np.asarray(want),
                               atol=1e-6)

    def test_quantize_params_rungs_and_aliases(self):
        from bigdl_tpu.models.transformer import TransformerLM
        m = TransformerLM(vocab_size=64, max_len=32, embed_dim=64,
                          num_heads=2, num_layers=1)
        params, _ = m.init(jax.random.PRNGKey(0))
        for alias, kind in (("int4", "q4"), ("fp8", "f8")):
            qp = quant.quantize_params(params, mode=alias,
                                       extra_keys=("tok",))
            assert quant.packed_kind(qp["tok"]) == kind
            blk = qp["blocks"][0]
            assert quant.packed_kind(blk["attn"]["wq"]) == kind
        with pytest.raises(ValueError):
            quant.quantize_params(params, mode="w2")

    def test_declared_budgets_hold(self):
        """bench-tune's rung gate, asserted in the fast tier: accuracy
        inside quant.RUNG_BUDGETS and resident bytes under the declared
        ratio of bf16 (0.30x int4 / 0.55x fp8)."""
        from bigdl_tpu.bench_tune import _bench_rungs
        rungs = _bench_rungs(smoke=True)
        assert set(rungs) == {"w4", "f8"}
        for mode, r in rungs.items():
            assert r["passed"], (mode, r)
        assert rungs["w4"]["resident_ratio_vs_bf16"] <= 0.30
        assert rungs["f8"]["resident_ratio_vs_bf16"] <= 0.55


# -- 4. fused int8 conv ------------------------------------------------------

class TestFusedConv:
    @pytest.mark.parametrize("shape",
                             [(2, 3, 9, 11, 5, 3),
                              (1, 8, 16, 16, 16, 3),
                              (2, 5, 7, 7, 6, 1)])
    def test_fused_matches_widen_ragged(self, shape, monkeypatch,
                                        interpret_mode):
        n, c, h, w_, o, kk = shape
        from bigdl_tpu.nn.conv import SpatialConvolution
        conv = SpatialConvolution(c, o, kk, kk, pad_w=kk // 2,
                                  pad_h=kk // 2)
        params = conv.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (n, c, h, w_))
        packed = dict(params)
        packed["weight"] = quant.pack(params["weight"])
        monkeypatch.setenv("BIGDL_TPU_CONV_FUSED", "1")
        assert conv._fused_int8_eligible(packed["weight"])
        fused, _ = conv.apply(packed, (), x)
        monkeypatch.setenv("BIGDL_TPU_CONV_FUSED", "0")
        widen, _ = conv.apply(packed, (), x)
        assert np.allclose(np.asarray(fused), np.asarray(widen),
                           atol=2e-3, rtol=1e-3)

    def test_eligibility_dispatch(self, monkeypatch):
        from bigdl_tpu.nn.conv import (SpatialConvolution,
                                       SpatialDilatedConvolution)
        monkeypatch.setenv("BIGDL_TPU_CONV_FUSED", "1")
        w = quant.pack(jnp.ones((8, 4, 3, 3)))
        assert SpatialConvolution(4, 8, 3, 3)._fused_int8_eligible(w)
        # strided / grouped / dilated / non-int8 keep the widen path
        assert not SpatialConvolution(4, 8, 3, 3, stride_w=2,
                                      stride_h=2) \
            ._fused_int8_eligible(w)
        assert not SpatialConvolution(4, 8, 3, 3, n_group=2) \
            ._fused_int8_eligible(quant.pack(jnp.ones((8, 2, 3, 3))))
        assert not SpatialDilatedConvolution(4, 8, 3, 3) \
            ._fused_int8_eligible(w)
        assert not SpatialConvolution(4, 8, 3, 3)._fused_int8_eligible(
            quant.pack(jnp.ones((8, 4, 3, 3)), mode="w4"))
        monkeypatch.setenv("BIGDL_TPU_CONV_FUSED", "0")
        assert not SpatialConvolution(4, 8, 3, 3) \
            ._fused_int8_eligible(w)

    def test_q4_conv_widens(self, interpret_mode):
        """A q4 conv weight serves through the widen fallback — same
        numbers as dequantizing by hand."""
        from bigdl_tpu.nn.conv import SpatialConvolution
        conv = SpatialConvolution(4, 8, 3, 3, pad_w=1, pad_h=1,
                                  with_bias=False)
        params = conv.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 8, 8))
        qt = quant.pack(params["weight"], mode="w4")
        got, _ = conv.apply({"weight": qt}, (), x)
        want, _ = conv.apply({"weight": quant.unpack(qt)}, (), x)
        assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# -- 5. paged attention + scheduler + CLI ------------------------------------

def _pool(rng, pages, hkv, ps, d, dtype=jnp.float32, poison=True):
    """A page pool ``(pages + 1, ps, W)`` of random K (or V): KV head
    ``j`` in lanes ``[j*d, (j+1)*d)``, the padding lanes zero."""
    from bigdl_tpu.ops.attention import paged_pool_width
    a = np.zeros((pages + 1, ps, paged_pool_width(hkv, d)), np.float32)
    a[..., :hkv * d] = rng.randn(pages + 1, ps, hkv * d)
    if poison:
        # the trash page holds NaN garbage: the kernel must zero it
        # exactly like the gather path's tmask (the full-capacity-
        # neighbor regression class — 0 * NaN poisons softmax sums)
        a[pages] = np.nan
    return jnp.asarray(a, dtype)


def _gather(q, kp, vp, pages, pos, scale, hkv):
    """The jnp gather path of ``apply_decode_pages`` on the pool
    ``(P + 1, ps, W)``: the oracle."""
    from bigdl_tpu.ops.attention import expand_kv_heads
    b, _, _, d = q.shape
    ps, lp, trash = kp.shape[1], pages.shape[1], kp.shape[0] - 1
    tmask = jnp.repeat(pages == trash, ps, axis=1)[:, None, :, None]

    def view(pool):
        v = pool[pages][..., :hkv * d].reshape(b, lp * ps, hkv, d)
        return jnp.where(tmask, 0, v.transpose(0, 2, 1, 3))

    kk, vv = expand_kv_heads(q, view(kp), view(vp))
    scores = jnp.einsum("bhsd,bhld->bhsl", q, kk) * scale
    valid = (jnp.arange(lp * ps)[None, None, :] <= pos[:, :, None])
    scores = jnp.where(valid[:, None], scores, -jnp.inf)
    wts = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return jnp.einsum("bhsl,bhld->bhsd", wts.astype(vv.dtype), vv)


def _shaped(q, kp, vp, pages, pos, scale, hkv, *, f32_scores=False,
            zero_trash=True):
    """The gather path's math, operation for operation, in products of
    the kernel's SHAPES and in its ORDER: the bit-exact oracle.  XLA's
    CPU backend picks its dot emitter by shape, so `_gather`'s per-head
    products over ``d`` lanes sum the same terms in another order than
    the kernel's; here a head's query is zero outside its own lanes of
    the chunk (a prefill bucket), or every head of a lane group is a
    row of one block-diagonal product over the group's lanes (few
    queries a head; a lone wide head is its own row), as `_paged_tiling`
    says.  Few queries are scored BLOCK by block (`paged_block_pages`
    pages each) up to the block of the row's last visible page; the
    softmax is the whole visible row's — the maximum over its blocks,
    the exponentials summed lane by lane in slot order and once across
    lanes, the division — and the weighted sum adds a block at a time
    into float32.  Written with plain indexing, not with the wrapper's
    reshapes.  ``f32_scores`` and ``zero_trash=False`` plant the two
    faults the parity gate exists for: scores left in float32 where the
    reference's einsum rounds to the promoted dtype, and a trash page
    read as it is."""
    from bigdl_tpu.ops import attention as A
    b, h, s, d = q.shape
    ps, width = A.paged_pool_dims(kp)
    lp, trash, group = pages.shape[1], kp.shape[0] - 1, h // hkv
    length = lp * ps
    groups, chunk, rows, _ = A._paged_tiling(
        hkv, group, s, length, d, ps, jnp.dtype(kp.dtype).itemsize)
    few = A._paged_few(hkv, group, s, length)
    n = A.paged_block_pages(ps, lp)
    bs = n * ps
    slots = -(-lp // n) * bs            # a row's key slots: whole blocks
    # the last page any query of a row sees; what lies behind it is not
    # copied, and reads as zero like a trash page
    last = np.clip(np.asarray(pos).max(axis=1) // ps, 0, lp - 1)
    dead = np.arange(lp)[None] > last[:, None]
    if zero_trash:
        dead = dead | (np.asarray(pages) == trash)
    dead = jnp.asarray(np.repeat(dead, ps, axis=1))[..., None]

    def view(pool):
        v = jnp.where(dead, 0, pool[pages].reshape(b, length, width))
        return jnp.pad(v, ((0, 0), (0, slots - length), (0, 0)))

    kk, vv = view(kp), view(vp)
    at = jnp.arange(slots)[None, None, :]
    valid = (at <= pos[:, :, None]) & (at < length)

    def scores(qr, k2, ok):
        # the accumulator is float32 on either path (a CPU's bf16 dot
        # upcasts, the MXU accumulates so); the ROUNDING of the scores
        # to the promoted dtype is the reference's
        sc = jnp.einsum("rc,lc->rl", qr, k2,
                        preferred_element_type=jnp.float32)
        if not f32_scores:
            sc = sc.astype(jnp.result_type(qr.dtype, k2.dtype))
        return jnp.where(ok, sc * scale, -jnp.inf).astype(jnp.float32)

    def weighted(w, v2):
        return jnp.einsum("rl,lc->rc", w.astype(v2.dtype), v2,
                          preferred_element_type=jnp.float32)

    def attend(qr, k2, v2, ok):
        # a prefill bucket: the whole table in one product a head
        w = jax.nn.softmax(scores(qr, k2[:length], ok[:, :length]), axis=-1)
        return weighted(w, v2[:length]).astype(kp.dtype)

    def attend_few(qr, k2, v2, ok, blocks):
        cols = [slice(j * bs, (j + 1) * bs) for j in range(blocks)]
        sc = [scores(qr, k2[c], ok[:, c]) for c in cols]
        top = jnp.full((qr.shape[0], 1), -jnp.inf, jnp.float32)
        for x in sc:
            top = jnp.maximum(top, jnp.max(x, axis=-1, keepdims=True))
        lane = math.gcd(bs, 128)
        total = jnp.zeros((qr.shape[0], lane), jnp.float32)
        ex = [jnp.exp(x - top) for x in sc]
        for e in ex:
            for c in range(0, bs, lane):
                total = total + e[:, c:c + lane]
        total = jnp.sum(total, axis=-1, keepdims=True)
        out = jnp.zeros((qr.shape[0], k2.shape[1]), jnp.float32)
        for e, c in zip(ex, cols):
            out = out + weighted(e / total, v2[c])
        return out.astype(kp.dtype)

    dp = d if hkv > 1 else width        # lanes a head takes in the pool
    hp = width // dp
    qh = jnp.pad(q.reshape(b, hkv, group * s, d),
                 ((0, 0), (0, hp - hkv), (0, 0), (0, dp - d)))
    out = [[None] * hp for _ in range(b)]
    for i in range(b):
        blocks = int(last[i]) // n + 1
        if rows:
            hg = hp // groups
            ok = jnp.tile(valid[i], (hg * group, 1))
            for g in range(groups):
                lanes = slice(g * hg * dp, (g + 1) * hg * dp)
                qr = jnp.zeros((hg, group * s, hg, dp), q.dtype)
                for j in range(hg):
                    qr = qr.at[j, :, j].set(qh[i, g * hg + j])
                o = attend_few(qr.reshape(hg * group * s, hg * dp),
                               kk[i, :, lanes], vv[i, :, lanes], ok, blocks)
                o = o.reshape(hg, group * s, hg, dp)
                for j in range(hg):
                    out[i][g * hg + j] = o[j, :, j]
            continue
        hc = chunk // dp
        ok = jnp.tile(valid[i], (group, 1))
        for j in range(hp):
            c, t = divmod(j, hc)
            lanes = slice(c * chunk, (c + 1) * chunk)
            qr = jnp.zeros((group * s, hc, dp), q.dtype).at[:, t].set(
                qh[i, j])
            qr = qr.reshape(group * s, chunk)
            o = attend_few(qr, kk[i, :, lanes], vv[i, :, lanes], ok,
                           blocks) if few else \
                attend(qr, kk[i, :, lanes], vv[i, :, lanes], ok)
            out[i][j] = o.reshape(group * s, hc, dp)[:, t]
    out = jnp.stack([jnp.stack(r) for r in out])    # (B, hp, group x S, dp)
    return out[:, :hkv, :, :d].reshape(b, h, s, d)


def _bits(want, got):
    """Bit for bit, NaN where NaN is."""
    want, got = np.asarray(want), np.asarray(got)
    assert want.dtype == got.dtype and want.shape == got.shape
    return np.array_equal(want.astype(np.float32), got.astype(np.float32),
                          equal_nan=True)


def _close(want, got):
    """`_shaped` against `_gather`: the same terms summed in another
    order (see `_shaped`), so a few units in the last place of the
    float32 accumulator, at most ONE of a bf16 result (2**-7 of it) —
    what a float32 score where the reference's is bf16 moves a result
    by, so that fault is held by `_bits`, not here."""
    want, got = np.asarray(want), np.asarray(got)
    assert want.dtype == got.dtype and want.shape == got.shape
    rtol, atol = (2.0 ** -7, 0) if want.dtype == jnp.bfloat16 \
        else (2e-6, 2e-6)
    return np.allclose(want.astype(np.float32), got.astype(np.float32),
                       rtol=rtol, atol=atol, equal_nan=True)


class TestPagedAttention:
    # (B, H, Hkv, S, D, table slots, key slots a block): pages of 16,
    # the shapes the cells run.  A row's context is drawn so that its
    # last page is partial and the rest of its table trash.  Blocks of
    # 3 pages cut these short tables into two or three, the last one
    # short of pages where the table is no multiple (4 and 8 slots).
    SHAPES = {
        # GPT-2 XL's heads: an odd count, the width padded 1,600 -> 1,664
        "gpt2xl_decode": (3, 25, 25, 1, 64, 4, 48),
        "gpt2xl_verify": (2, 25, 25, 4, 64, 4, 48),
        "gpt2xl_prefill": (1, 25, 25, 64, 64, 8, 48),
        "gqa4_decode": (3, 8, 2, 1, 64, 4, 48),
        "gqa4_prefill": (1, 8, 2, 48, 64, 6, 48),
        # the latent pool: ONE head of 576 read by 32 query rows
        "latent_decode": (2, 1, 1, 32, 576, 8, 48),
        "head128_verify": (2, 4, 4, 4, 128, 4, 48),
        # the block the cells run with, 8 pages = 128 key slots, over a
        # table of two and a half of them
        "gpt2xl_decode_blocks": (3, 25, 25, 1, 64, 20, 128),
        "latent_decode_blocks": (2, 1, 1, 32, 576, 20, 128),
    }

    def _case(self, monkeypatch, shape, dtype):
        """(q, K pool, V pool, tables, positions, scale, Hkv) of a
        shape: NaN in the trash page, the rest of a row's table trash
        and one slot inside the longest row's walk.
        The scale is a Python float, as the layer's: a numpy scalar is
        no weak type and would promote bf16 scores to float32."""
        from bigdl_tpu.ops import attention as A
        b, h, hkv, s, d, lp, block = self.SHAPES[shape]
        monkeypatch.setattr(A, "_PAGED_BLOCK_SLOTS", block)
        assert -(-lp // A.paged_block_pages(16, lp)) > 1
        if shape.endswith("prefill"):
            # a bucket of 256 over a table of 1,024 has 26 MB of scores;
            # this small one takes the same form under a smaller bound
            monkeypatch.setattr(A, "_PAGED_BATCHED_SCORES", 64 * 1024)
        ps, p = 16, b * lp
        rng = np.random.RandomState(1)
        kp, vp = (_pool(rng, p, hkv, ps, d, dtype) for _ in "kv")
        q = jnp.asarray(rng.randn(b, h, s, d), dtype)
        ctx = rng.randint(0, lp * ps - s, size=b)
        # the longest: its last page partial, and the table's last slot
        # trash where no shorter row has trash slots
        ctx[0] = (lp - (b == 1)) * ps - s - 3
        pos = ctx[:, None] + np.arange(s)[None]
        pages = np.full((b, lp), p, np.int32)
        for i in range(b):
            used = pos[i, -1] // ps + 1
            pages[i, :used] = rng.permutation(p)[:used]
        pages[0, 1] = p         # an unmapped hole INSIDE the longest walk
        pages, pos = jnp.asarray(pages), jnp.asarray(pos, jnp.int32)
        return q, kp, vp, pages, pos, 1.0 / math.sqrt(d), hkv

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("shape", sorted(SHAPES))
    def test_kernel_bit_parity_vs_gather(self, interpret_mode, monkeypatch,
                                         shape, dtype):
        """The kernel against the gather path on the token-major pool,
        NaN in the trash page, at the shapes that matter: bit for bit
        against the gather path's math in products of the kernel's
        shapes, and that within the summation order of `_gather`."""
        from bigdl_tpu.ops import attention as A
        args = self._case(monkeypatch, shape, dtype)
        (_, h, s, d), hkv, lp = args[0].shape, args[-1], args[3].shape[1]
        rows = A._paged_tiling(hkv, h // hkv, s, lp * 16, d, 16,
                               jnp.dtype(dtype).itemsize)[2]
        # few queries a head: scored block by block, every head a row
        # of one product
        few = not shape.endswith("prefill")
        assert A._paged_few(hkv, h // hkv, s, lp * 16) == few
        assert rows == (few and hkv > 1)
        got = A.paged_attention(*args[:-1], num_kv_heads=hkv)
        assert np.isfinite(np.asarray(got, np.float32)).all()
        ref = _shaped(*args)
        assert _bits(ref, got)
        assert _close(_gather(*args), ref)

    @pytest.mark.parametrize("fault", ["f32_scores", "trash_as_it_is"])
    @pytest.mark.parametrize("shape", ["gpt2xl_decode", "gpt2xl_prefill",
                                       "latent_decode"])
    def test_bit_parity_sees_planted_fault(self, interpret_mode,
                                           monkeypatch, shape, fault):
        """What the gate above is for, in each of the kernel's forms on
        a bf16 cache: the oracle with the fault planted is NOT what the
        kernel returns — so a kernel with that fault fails the gate."""
        from bigdl_tpu.ops import attention as A
        args = self._case(monkeypatch, shape, "bfloat16")
        got = A.paged_attention(*args[:-1], num_kv_heads=args[-1])
        assert _bits(_shaped(*args), got)
        planted = _shaped(*args, f32_scores=fault == "f32_scores",
                          zero_trash=fault != "trash_as_it_is")
        assert not _bits(planted, got)

    @staticmethod
    def _paths(monkeypatch, run):
        """``run()`` through the kernel, through `_shaped` in the
        kernel's place, and through the layer's own gather path."""
        from bigdl_tpu.ops import attention as A
        outs = {"kernel": run()}
        with monkeypatch.context() as patch:
            patch.setattr(A, "paged_attention", lambda *a, num_kv_heads:
                          _shaped(*a, num_kv_heads))
            outs["shaped"] = run()
        with monkeypatch.context() as patch:
            patch.setattr(A, "paged_attention_enabled", lambda: False)
            outs["gather"] = run()
        return outs

    def test_kernel_bit_parity_bf16_cache(self, interpret_mode,
                                          monkeypatch):
        """bf16 caches are the regression class the f32-only parity
        test missed: an eager f32 promotion inside the kernel diverges
        from the reference einsum's jnp promotion (bf16 x bf16 scores
        stay bf16 there).  Full-layer check, kernel on vs off, with a
        NaN-poisoned trash page."""
        from bigdl_tpu.nn.attention import MultiHeadAttention
        attn = MultiHeadAttention(32, 4, num_kv_heads=2, rope=True)
        params = attn.init_params(jax.random.PRNGKey(0))
        params = jax.tree_util.tree_map(
            lambda leaf: leaf.astype(jnp.bfloat16), params)
        cache = attn.init_paged_cache(10, 4, jnp.bfloat16)
        nanb = jnp.asarray(np.nan, jnp.bfloat16)
        cache = {"k": cache["k"].at[10].set(nanb),
                 "v": cache["v"].at[10].set(nanb)}
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 32),
                              jnp.bfloat16)
        pages = np.full((2, 8), 10, np.int32)
        pages[0, :4] = [0, 1, 2, 3]
        pages[1, :2] = [4, 5]
        pages = jnp.asarray(pages)
        pos = jnp.asarray([12, 4], jnp.int32)
        active = jnp.asarray([True, True])
        outs = self._paths(monkeypatch, lambda: attn.apply_decode_pages(
            params, x, dict(cache), pages, pos, active)[0])
        assert np.isfinite(np.asarray(outs["kernel"], np.float32)).all()
        assert _bits(outs["shaped"], outs["kernel"])
        assert _close(outs["gather"], outs["shaped"])

    # first query position of each row (``ctx``) or its last (``end``),
    # in the grid's order; tables of 4 pages of 16 in blocks of 2 pages
    # (32 key slots) unless the case says otherwise.  A row's table maps
    # the pages its queries reach.
    WALKS = {
        # the shortest on its first page only, the longest filling its
        # table, in one call
        "mixed_contexts": dict(ctx=[3, 59, 20]),
        # a trash slot INSIDE the walk (an unmapped hole before the
        # row's last visible page)
        "trash_inside_walk": dict(ctx=[40, 59], hole={0: 1}),
        # a short row after a long one: its scratch past page 0 holds
        # the long row's (finite) keys unless the kernel zeroes it
        "stale_finite": dict(ctx=[59, 2, 35, 1]),
        # the same with NaN where the long row itself cannot see: its
        # own output is NaN on both paths (0 x NaN), the short rows
        # after it must stay finite
        "stale_nan": dict(ctx=[50, 2, 1], nan_after=0),
        # a context that ends on a block's last slot, and one that ends
        # on the next block's first
        "block_edges": dict(end=[31, 32, 63, 0]),
        # a table that is no multiple of the block: 5 slots in blocks
        # of 2, the third block one page short
        "table_not_whole_blocks": dict(end=[79, 3, 66], lp=5),
        # a full table (nothing to skip) beside a one-token row
        "full_beside_one_token": dict(end=[63, 0, 63]),
        # a whole block of NaN keys and values behind the first row's
        # last visible slot (the first of its second block): the rows
        # after it, the third in the same half of the scratch, hold
        # their first block only and must not read it
        "stale_nan_block": dict(end=[32, 2, 1, 5], nan_after=0,
                                nan_keys=True),
        # trash holes in the middle of a block of 4 pages, 6 slots
        "trash_mid_block": dict(end=[90, 60], lp=6, block=64,
                                hole={0: 2, 1: 1}),
    }

    @pytest.mark.parametrize("s,variant", [
        (1, "rows"), (1, "rows_lane_groups"), (5, "rows"),
        (5, "rows_lane_groups"), (5, "chunks"), (5, "chunk_groups")])
    @pytest.mark.parametrize("case", sorted(WALKS))
    def test_walk_bit_parity_vs_gather(self, interpret_mode, monkeypatch,
                                   case, s, variant):
        """The bounded walk against the gather path: GQA group 2 over
        an ODD number of KV heads (three of 64 in a width of 256),
        pages of 16 in blocks of 2, a decode step and a 5-token verify;
        every head a row of one product over the whole width, the width
        split into two lane groups (a group's scratch then follows the
        step two before it, its own row's or the previous row's), and
        chunk by chunk, head by head, in one lane group and in two."""
        from bigdl_tpu.ops import attention as A
        spec = self.WALKS[case]
        hkv, d, ps, lp = 3, 64, 16, spec.get("lp", 4)
        monkeypatch.setattr(A, "_PAGED_BLOCK_SLOTS", spec.get("block", 32))
        limit = A._PAGED_VMEM[1]
        groups = 2 if variant.endswith("groups") else 1
        rows = variant.startswith("rows")
        if not rows:
            monkeypatch.setattr(A, "_PAGED_BATCHED_SCORES", 0)
        if groups == 2:
            # room for one chunk (two heads of 64) a step, not for two
            block = A.paged_block_pages(ps, lp) * ps
            small = (A._paged_step_bytes(
                128, 2, 2, s, -(-lp * ps // block) * block, 4, rows, rows),
                limit)
            monkeypatch.setattr(A, "_PAGED_VMEM", small)
            # (and no wide plan to take the row whole after all)
            monkeypatch.setattr(A, "_PAGED_VMEM_WIDE", small)
        h = 2 * hkv
        assert A._paged_tiling(hkv, 2, s, lp * ps, d, ps, 4) == (
            groups, 256 // groups if rows else 128, rows, limit)
        assert A._paged_few(hkv, 2, s, lp * ps) == rows
        if "end" in spec:
            ctx = np.maximum(np.asarray(spec["end"]) - (s - 1), 0)
        else:
            ctx = np.asarray(spec["ctx"])
        b = len(ctx)
        rng = np.random.RandomState(3)
        p = b * lp
        q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
        kp, vp = (_pool(rng, p, hkv, ps, d) for _ in "kv")
        pos = ctx[:, None] + np.arange(s)[None]
        pages = np.full((b, lp), p, np.int32)
        for i in range(b):
            used = min(lp, pos[i, -1] // ps + 1)
            pages[i, :used] = np.arange(i * lp, i * lp + used)
        for i, slot in spec.get("hole", {}).items():
            assert slot < pos[i, -1] // ps
            pages[i, slot] = p
        if "nan_after" in spec:
            i = spec["nan_after"]
            page, off = divmod(int(pos[i, -1]) + 1, ps)
            assert 0 < off and page == pos[i, -1] // ps
            vp = vp.at[pages[i, page], off:, :hkv * d].set(jnp.nan)
            if spec.get("nan_keys"):
                kp = kp.at[pages[i, page], off:, :hkv * d].set(jnp.nan)
        pages, pos = jnp.asarray(pages), jnp.asarray(pos, jnp.int32)
        args = q, kp, vp, pages, pos, 1.0 / math.sqrt(d), hkv
        got = np.asarray(A.paged_attention(*args[:-1], num_kv_heads=hkv))
        clean = [i for i in range(b) if i != spec.get("nan_after")]
        assert np.isfinite(got[clean]).all()
        assert np.isnan(got).any() == ("nan_after" in spec)
        ref = _shaped(*args)
        assert _bits(ref, got)
        assert _close(_gather(*args), ref)

    @pytest.mark.parametrize("s", [1, 256, 768])
    def test_grid_moves_whole_pages(self, s):
        """Structural guard at GPT-2 XL's shapes: ONE kernel call, the
        pools reach it as they are and stay in HBM (no block of theirs
        is cut out for a step: the kernel copies whole pages, 16 tokens
        x 1,664 lanes with every KV head in them, itself).  A grid step
        is one row, so the grid is rows x 1 lane group; a row's walk is
        8 BLOCKS of 8 pages (a copy semaphore each, in the two halves
        of a two-row scratch), not 64 pages, and nothing has the heads'
        extent."""
        from jax.experimental import pallas as pl
        from bigdl_tpu.ops import attention as A
        b, h, d, ps, lp = (10 if s == 1 else 1), 25, 64, 16, 64
        width = A.paged_pool_width(h, d)
        assert width == 1664
        pool = jax.ShapeDtypeStruct((b * lp + 1, ps, width), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(
            lambda *a: A.paged_attention(*a, 0.125))(
            jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16), pool, pool,
            jax.ShapeDtypeStruct((b, lp), jnp.int32),
            jax.ShapeDtypeStruct((b, s), jnp.int32))
        calls = [e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "pallas_call"]
        assert len(calls) == 1
        assert calls[0].params["name"] == "paged_attention"
        # the pools go from the arguments into the call untouched
        assert [v for v in calls[0].invars
                if v in jaxpr.jaxpr.invars[1:3]] == jaxpr.jaxpr.invars[1:3]
        mapping = calls[0].params["grid_mapping"]
        groups, chunk, rows, limit = A._paged_tiling(h, 1, s, lp * ps, d,
                                                     ps, 2)
        # a decode step scores every head as a row over the whole
        # width, block by block; a prefill bucket goes pair by pair of
        # heads over the whole table
        assert (groups, chunk, rows) == ((1, width, True) if s == 1
                                         else (1, 128, False))
        assert A._paged_few(h, 1, s, lp * ps) == (s == 1)
        assert limit == A._PAGED_VMEM[1]
        block = A.paged_block_pages(ps, lp)
        assert block * ps == 128 and lp // block == 8
        assert tuple(mapping.grid) == (b, 1)
        pools = [m.block_aval for m in mapping.block_mappings
                 if m.array_aval.shape == pool.shape]
        assert len(pools) == 2 and all(
            a.shape == pool.shape and a.memory_space == pl.ANY
            for a in pools)
        scratch = [getattr(v.aval, "shape", None)
                   for v in calls[0].params["jaxpr"].invars[
                       -mapping.num_scratch_operands:]]
        assert scratch[:3] == [(2, lp * ps, width), (2, lp * ps, width),
                               (2, lp // block)]
        # few queries keep the row's scores block by block, and its
        # float32 output
        assert scratch[3:] == ([(lp // block, 26, block * ps), (26, width)]
                               if s == 1 else [])
        assert h not in mapping.grid

    def test_decode_pages_kernel_on_off_bit_equal(self, interpret_mode,
                                                  monkeypatch):
        """The integration gate: TransformerLM.decode_pages (GQA +
        rope) with the kernel vs the jnp gather path — including rows
        whose tables hold trash entries."""
        from bigdl_tpu.models.transformer import TransformerLM
        m = TransformerLM(vocab_size=64, max_len=64, embed_dim=32,
                          num_heads=4, num_kv_heads=2, num_layers=2,
                          position="rope")
        params, state = m.init(jax.random.PRNGKey(0))
        cache = m.init_paged_cache(num_pages=12, page_size=4)
        trash = 12
        pages = np.full((2, 16), trash, np.int32)
        pages[0, :4] = [0, 1, 2, 3]
        pages[1, :2] = [4, 5]
        pages = jnp.asarray(pages)
        toks = jnp.asarray([[5, 9], [11, 3]], jnp.int32)
        pos = jnp.asarray([12, 4], jnp.int32)
        active = jnp.asarray([True, True])

        def run():
            lp, new_cache = m.decode_pages(params, state, toks,
                                           [dict(c) for c in cache],
                                           pages, pos, active)
            return [lp] + [c["k"] for c in new_cache]

        outs = self._paths(monkeypatch, run)
        for kern, shaped, gather in zip(*(outs[k] for k in (
                "kernel", "shaped", "gather"))):
            assert _bits(shaped, kern)
            assert _close(gather, shaped)
        # the first layer's write does not pass through the read
        assert _bits(outs["gather"][1], outs["kernel"][1])

    def test_generator_paged_kernel_end_to_end(self, interpret_mode,
                                               monkeypatch):
        """The generator's ONE decode program, the scan of
        ``decode_pages``, with its reads through the kernel
        (interpreted) and through the layer's gather: the same tokens,
        and ``TransformerLM.generate()``'s, bit for bit — including a
        FULL-CAPACITY request beside an active neighbor (the NaN
        regression scenario r11 pinned)."""
        from bigdl_tpu.models.transformer import TransformerLM
        from bigdl_tpu.ops import attention as A
        from bigdl_tpu.serving.scheduler.continuous import \
            ContinuousGenerator
        m = TransformerLM(vocab_size=64, max_len=32, embed_dim=32,
                          num_heads=2, num_layers=2)
        params, state = m.init(jax.random.PRNGKey(0))
        m.params, m.state = params, state
        # request 0 fills its cache to max_len exactly; request 1 is
        # the neighbor that must stay finite and identical
        prompts = [np.arange(1, 25), np.arange(2, 10)]
        outs = {}
        for kern in (True, False):
            with monkeypatch.context() as patch:
                if not kern:
                    patch.setattr(A, "paged_attention_enabled",
                                  lambda: False)
                g = ContinuousGenerator(m, num_slots=2, max_len=32,
                                        steps_per_sync=3, page_size=4)
                try:
                    assert g.stats()["paged_kernel"] is kern
                    outs[kern] = g.generate(prompts, 8)
                finally:
                    g.drain()
        for p, gather, kernel in zip(prompts, outs[False], outs[True]):
            want = np.asarray(m.generate(params, state, p[None], max_new=8,
                                         temperature=0.0))[0]
            assert np.array_equal(gather, want)
            assert np.array_equal(kernel, want)


class TestCliAndReport:
    def test_tune_smoke_artifact_and_cache(self, tmp_path, monkeypatch):
        from bigdl_tpu.bench_tune import main as tune_main
        from bigdl_tpu.observability import ledger
        run_dir = str(tmp_path / "run")
        monkeypatch.setenv("BIGDL_TPU_RUN_DIR", run_dir)
        ledger.set_run_dir(run_dir)
        out = str(tmp_path / "BENCH_tune.json")
        store = str(tmp_path / "store")
        try:
            assert tune_main(["--smoke", "--tune-dir", store,
                              "--out", out]) == 0
            # second run serves every key from the warm store
            assert tune_main(["--smoke", "--tune-dir", store,
                              "--out", out]) == 0
        finally:
            ledger.flush()
            ledger.set_run_dir(None)
            tuning.set_tune_dir(None)
        with open(out) as f:
            art = json.load(f)
        assert art["gate"]["passed"]
        assert art["swept"] == 0 and art["cache_hits"] > 0
        assert art["conv"]["ge_widen"]
        for mode in ("w4", "f8"):
            assert art["rungs"][mode]["passed"]
        # every swept op >= 1.0x its fallback (regression gate)
        with open(os.path.join(store,
                               f"tune-{tuning.platform()}.json")) as f:
            entries = json.load(f)["entries"]
        assert entries and all(e["speedup"] >= 1.0
                               for e in entries.values())

        # tune.run ledger -> run-report "kernel tuning" section + json
        recs = []
        for fname in glob.glob(os.path.join(run_dir,
                                            "events-*.jsonl")):
            with open(fname) as fh:
                recs += [json.loads(line) for line in fh]
        assert any(r.get("type") == "tune.run" for r in recs)
        from bigdl_tpu.observability.report import (build_report,
                                                    load_ledger,
                                                    render_report)
        rep = build_report(load_ledger(run_dir)[0])
        assert rep["tuning"]["swept"] + rep["tuning"]["cache_hits"] > 0
        assert rep["tuning"]["winners"]
        assert "kernel tuning" in render_report(rep)

    def test_report_tuning_section_from_records(self):
        from bigdl_tpu.observability.report import (build_report,
                                                    render_report)
        recs = [{"type": "tune.run", "_pid": 1, "mono": 0.0,
                 "platform": "cpu", "ops": ["lrn"], "swept": 2,
                 "cache_hits": 3,
                 "winners": {"lrn|c8f256|f32": {"tiles": [128],
                                                "speedup": 1.5}},
                 "store": "/x/tune-cpu.json"}]
        rep = build_report(recs)
        assert rep["tuning"]["cache_hits"] == 3
        assert rep["tuning"]["max_speedup"] == 1.5
        assert "kernel tuning" in render_report(rep)
        # absent records -> None, and the renderer stays quiet
        assert build_report([])["tuning"] is None
