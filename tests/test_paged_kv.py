"""Paged KV-cache subsystem (r11 tentpole): allocator property tests,
COW break-on-write, the unified page-indirect kernel's interpret-mode
parity (the tests/test_decode_attention.py pattern — exact kernel code
paths on the CPU backend), token-identical greedy parity of the
engine vs ``llama.generate`` on the r7 serving workload, pages-free
admission with the ``max_len`` provisioning wall removed, and the
one-sync-per-segment audit over the paged serve loop."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.ops.pallas.paged_attention as pa
from paddle_tpu.inference.paged_kv import PageAllocator, PagedKVCache
from paddle_tpu.inference.prefix_cache import PagedPrefixCache
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import llama
from paddle_tpu.parallel import set_mesh


@pytest.fixture(scope="module")
def tiny(tiny_llama):
    # r12: model build hoisted to the session-scoped conftest fixture
    set_mesh(None)
    return tiny_llama


def _dense_reference(cfg, params, prompt, n):
    out = llama.generate(params, np.asarray(prompt, np.int32)[None], cfg,
                         max_new_tokens=n, max_len=96)
    return [int(t) for t in np.asarray(out)[0]]


# ---------------------------------------------------------------------------
# allocator property tests (satellite 1)
# ---------------------------------------------------------------------------


class TestPageAllocator:
    def test_alloc_free_refcount_roundtrip(self):
        a = PageAllocator(9)                      # 8 usable + trash
        assert a.pages_free == 8
        pages = a.alloc(3)
        assert a.pages_free == 5 and all(a.ref(p) == 1 for p in pages)
        a.retain(pages[:2])                       # COW share
        assert [a.ref(p) for p in pages] == [2, 2, 1]
        assert a.release(pages) == 1              # only the unshared frees
        assert a.pages_free == 6
        assert a.release(pages[:2]) == 2
        assert a.pages_free == 8
        assert a.check() == []

    def test_misuse_raises(self):
        a = PageAllocator(5)
        pages = a.alloc(2)
        a.release(pages)
        with pytest.raises(RuntimeError, match="double free"):
            a.release(pages[:1])
        with pytest.raises(RuntimeError, match="unallocated"):
            a.retain([pages[0]])
        with pytest.raises(RuntimeError, match="exhausted"):
            a.alloc(5)
        assert a.check() == []

    def test_randomized_schedule_no_leak_no_double_free(self):
        """Randomized admit / COW-share / finish / preempt schedule: the
        free-list + refcount invariant must hold at every step and every
        page must come back once everything retires."""
        rng = np.random.RandomState(0)
        a = PageAllocator(33)                     # 32 usable
        live = []                                 # reservations: page lists
        for step in range(300):
            op = rng.randint(4)
            if op == 0 and a.pages_free >= 4:     # admit
                live.append(a.alloc(int(rng.randint(1, 5))))
            elif op == 1 and live:                # COW prefix share
                src = live[rng.randint(len(live))]
                k = int(rng.randint(1, len(src) + 1))
                shared = src[:k]
                a.retain(shared)
                extra = (a.alloc(int(rng.randint(0, min(3, a.pages_free)
                                                 + 1)))
                         if a.pages_free else [])
                live.append(shared + extra)
            elif op == 2 and live:                # finish
                a.release(live.pop(rng.randint(len(live))))
            elif op == 3 and live:                # preempt: free + resume
                idx = rng.randint(len(live))
                pages = live.pop(idx)
                a.release(pages)
                if a.pages_free >= len(pages):
                    live.append(a.alloc(len(pages)))
            assert a.check() == [], f"invariant broke at step {step}"
        for pages in live:
            a.release(pages)
        assert a.check() == []
        assert a.pages_free == 32


class TestCopyOnWrite:
    def test_break_on_write_gives_private_page(self, tiny):
        """fork -> shared pages (ref 2, zero copies); ensure_writable on
        the sharer -> ONE private page copy whose mutation leaves the
        original bit-identical; unshared pages break for free."""
        cfg, _ = tiny
        pgr = PagedKVCache(cfg, slots=2, page_size=8, num_pages=9,
                           max_pages=4)
        pages, row = pgr.reserve(16)              # 2 pages for slot 0
        pgr.install(0, pages)
        pgr.page_table = pgr.page_table.at[0].set(jnp.asarray(row))
        marker = jnp.ones_like(pgr.pool["k"][:, pages[0]]) * 7.0
        pgr.pool["k"] = pgr.pool["k"].at[:, pages[0]].set(marker)

        pgr.fork_slot(0, 1)                       # ref bump only
        assert pgr.slot_pages[1] == pages
        assert pgr.allocator.ref(pages[0]) == 2
        assert pgr.cow_breaks == 0

        new = pgr.ensure_writable(1, 0)           # break on write
        assert new != pages[0] and pgr.cow_breaks == 1
        assert pgr.allocator.ref(pages[0]) == 1
        np.testing.assert_array_equal(np.asarray(pgr.pool["k"][:, new]),
                                      np.asarray(marker))
        pgr.pool["k"] = pgr.pool["k"].at[:, new].set(marker * 2)
        np.testing.assert_array_equal(
            np.asarray(pgr.pool["k"][:, pages[0]]), np.asarray(marker))
        # already-private page: no further copy
        assert pgr.ensure_writable(1, 0) == new
        assert pgr.cow_breaks == 1
        pgr.free_slot(0)
        pgr.free_slot(1)
        assert pgr.leak_report() == []


# ---------------------------------------------------------------------------
# unified page-indirect kernel (interpret-mode parity, r6 pattern)
# ---------------------------------------------------------------------------


def _paged_reference(params, tokens, cfg, cache, pos, quant_dtype=None,
                     logits_all=False):
    """The dense-cache forward the paged one must equal: plain unrolled
    layers over a contiguous [L, B, S, Hkv, D] cache (plus [L, B, S]
    scale planes when ``quant_dtype``), every row of slot b written at
    ``pos[b] + t`` and the whole window attended. No pages, no loop
    carry, no kernel — the same projections and the same row math."""
    from paddle_tpu.quantization.serving import quantize_kv_rows

    B, T = tokens.shape
    positions = pos[:, None] + jnp.arange(T)
    rows = jnp.arange(B)[:, None]
    x = params["embed"].astype(cfg.dtype)[tokens]
    lw = llama.layer_params(params, cfg)
    cache = dict(cache)
    for i in range(cfg.num_layers):
        lp = {n: w[i] for n, w in lw.items()}
        q, new_k, new_v = llama._qkv_proj(cfg, x, lp, positions)
        win = {}
        for n, new in (("k", new_k), ("v", new_v)):
            if quant_dtype is not None:
                new, sc = quantize_kv_rows(new, quant_dtype)
                cache[n + "s"] = cache[n + "s"].at[i, rows, positions].set(sc)
            cache[n] = cache[n].at[i, rows, positions].set(
                new.astype(cache[n].dtype))
            win[n] = cache[n][i]
            if quant_dtype is not None:
                win[n] = win[n].astype(cfg.dtype) * cache[n + "s"][i][
                    ..., None, None].astype(cfg.dtype)
        attn = llama._dense_cache_attention(cfg, q, win["k"], win["v"],
                                            positions)
        x = llama._layer_post(cfg, x, attn, lp)
    return llama._head_logits(cfg, params, x, False,
                              logits_all=logits_all), cache


def _to_pages(plane, pt, psz):
    """Dense rows [L, B, S, Hkv, D] (scales [L, B, S]) -> the pool's
    planes [L, P, psz, Hkv*D] ([L, P, psz]) under page table ``pt``
    (pages no slot names stay zero)."""
    L, B, S = plane.shape[:3]
    flat = plane.reshape(L, B, S // psz, psz, -1)
    out = np.zeros((L, int(pt.max()) + 1, psz, flat.shape[-1]), plane.dtype)
    for b in range(B):
        out[:, pt[b]] = flat[:, b]
    return out[..., 0] if plane.ndim == 3 else out


class TestCarriedFlatPool:
    """``forward_with_pages`` on the pool as it lies ([L, P, psz, Hkv*D],
    carried through the layer loop, rows scattered in place) against the
    dense-cache forward: same logits, same rows in every plane."""

    @staticmethod
    def check(kind, T, B=3, hidden=64, dtype=jnp.float32, logits_all=False,
              **cfg_over):
        set_mesh(None)
        cfg = llama.LlamaConfig(
            vocab_size=96, hidden_size=hidden, intermediate_size=2 * hidden,
            num_layers=3, num_heads=4, num_kv_heads=2, max_seq_len=128,
            dtype=dtype, remat=False, **cfg_over)
        params = llama.init_params(cfg, jax.random.PRNGKey(1), dtype=dtype)
        rng = np.random.RandomState(5)
        S, psz, L = 128, 8, cfg.num_layers
        quant = kind == "int8"
        kv_dt = jnp.int8 if quant else jnp.bfloat16
        shape = (L, B, S, cfg.num_kv_heads, cfg.head_dim)
        if quant:
            cache = {n: jnp.asarray(rng.randint(-127, 128, shape), kv_dt)
                     for n in ("k", "v")}
            cache.update({n: jnp.asarray(rng.rand(L, B, S) * 0.01 + 1e-3,
                                         jnp.float32) for n in ("ks", "vs")})
        else:
            cache = {n: jnp.asarray(rng.randn(*shape) * 0.3, kv_dt)
                     for n in ("k", "v")}
        pt = rng.permutation(np.arange(1, 1 + B * S // psz)) \
            .reshape(B, S // psz).astype(np.int32)
        pool = {n: jnp.asarray(_to_pages(np.asarray(a), pt, psz))
                for n, a in cache.items()}
        assert pool["k"].shape == (L, 1 + B * S // psz, psz,
                                   cfg.num_kv_heads * cfg.head_dim)
        toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, T)), jnp.int32)
        pos = jnp.asarray([9, 30, 17, 0, 41][:B], jnp.int32)
        live = jnp.arange(B) != 1                    # slot 1 is retired
        ref_l, ref_cache = _paged_reference(
            params, toks, cfg, cache, pos, kv_dt if quant else None,
            logits_all)
        out_l, out_pool = jax.jit(
            lambda pool: llama.forward_with_pages(
                params, toks, cfg, pool, jnp.asarray(pt), pos, live=live,
                logits_all=logits_all))(pool)
        alive = np.asarray(live)
        # logits feel a row that rounded the other way (see below) by
        # ~1e-3; a wrong page, row or layer moves them by ~1
        tol = 1e-2 if dtype == jnp.float32 else 6e-2
        assert out_l.shape == ref_l.shape
        np.testing.assert_allclose(np.asarray(out_l)[alive],
                                   np.asarray(ref_l)[alive],
                                   rtol=tol, atol=tol)
        assert set(out_pool) == set(pool)
        for n, a in out_pool.items():
            # live slots: the dense forward's rows, page for page; the
            # retired slot's pages: untouched (its write went to trash
            # page 0, which nobody reads)
            ref, was = (np.asarray(c[n]).astype(np.float32)
                        for c in (ref_cache, cache))
            keep = alive.reshape((1, B) + (1,) * (ref.ndim - 2))
            want = _to_pages(np.where(keep, ref, was), pt, psz)
            # a written row may round the other way (jit against eager)
            # and later layers' rows feel it by ~1e-3: a step of the
            # plane's dtype, where a wrong page or row is off by ~0.3
            atol = {"k": 5e-3, "v": 5e-3}.get(n, 1e-6)
            if dtype != jnp.float32:
                # bf16 activations: the kernel rounds each product of
                # the rotation, XLA's fusion their sum, so a row parts by
                # two steps of its OPERANDS (~4 here), whatever the sum
                atol = 7e-2
            np.testing.assert_allclose(
                np.asarray(a).astype(np.float32)[:, 1:], want[:, 1:],
                rtol=2 ** -7 if dtype == jnp.float32 else 2 ** -6,
                atol=1.0 if a.dtype == jnp.int8 else atol, err_msg=n)

    @pytest.mark.parametrize("T", [1, 16])
    @pytest.mark.parametrize("kind", ["bf16", "int8"])
    @pytest.mark.parametrize("scan_layers", [True, False])
    def test_matches_dense_cache_forward(self, scan_layers, kind, T):
        self.check(kind, T, scan_layers=scan_layers)

    FUSED = {
        "tick": dict(T=1), "chunk16": dict(T=16), "admit64": dict(T=64),
        "verify_b5_t5": dict(T=5, B=5, logits_all=True),
        "bf16_admit64": dict(T=64, dtype=jnp.bfloat16),
        "int8_pool_chunk16": dict(T=16, kind="int8"),
        "gridded_admit64": dict(T=64),
        "gridded_ragged_b5_t5": dict(T=5, B=5)}

    @pytest.mark.parametrize("case", sorted(FUSED))
    def test_fused_rows_match_the_xla_chain(self, monkeypatch, case):
        """With the tick's fused kernels active (interpreted here)
        ``forward_with_pages`` computes q, k, v and their rope by ONE
        formulation over flat rows at every T (``_rows_qkv``: a tick, a
        chunk, an admission, the verify tick's B x (K+1)); the reference
        keeps the XLA chain (``_qkv_proj``). Same logits, the same rows
        in every plane, a dead slot and each slot at its own base
        position; ``gridded``: the kernels cut the rows into blocks."""
        from paddle_tpu.ops.pallas import tick_fusion as tf

        monkeypatch.setattr(tf, "FORCE_INTERPRET", True)
        seen = []
        real = tf.fused_rope_qk
        monkeypatch.setattr(tf, "fused_rope_qk", lambda zq, *a: (
            seen.append(zq.shape[0]), real(zq, *a))[1])
        if case.startswith("gridded"):
            # 128-wide rows of float32: blocks of 16 rows
            monkeypatch.setattr(tf, "_BLOCK_BYTES", 16 * 1024)
        how = {"kind": "bf16", "B": 3, **self.FUSED[case]}
        self.check(hidden=128, **how)
        assert seen == [how["B"] * how["T"]]    # one site in the layer scan

    @pytest.mark.parametrize("rows", [64, 40])
    def test_gridded_kernels_equal_the_single_block(self, monkeypatch, rows):
        """``fused_rms_norm`` and ``fused_rope_qk`` over row blocks (the
        form rows x width beyond one block take; 40 rows: a ragged last
        block) give the single block's values bit for bit."""
        from paddle_tpu.ops.pallas import tick_fusion as tf

        monkeypatch.setattr(tf, "FORCE_INTERPRET", True)
        rng = np.random.RandomState(rows)
        x, zk = (jnp.asarray(rng.randn(rows, w), jnp.bfloat16)
                 for w in (256, 128))
        w = jnp.asarray(rng.rand(256) + 0.5, jnp.float32)
        pos = jnp.asarray(rng.randint(0, 1024, rows), jnp.int32)

        def both():
            return (tf.fused_rms_norm(x, w, 1e-5),
                    *tf.fused_rope_qk(x, zk, pos, 64, 1e6))

        def grid():
            return tf._row_grid(rows, 2, (256,), 256, whole=(256,))

        assert grid() == {}
        whole = both()
        monkeypatch.setattr(tf, "_BLOCK_BYTES", 16 * 1024)   # 16 rows
        assert grid()["grid"] == (-(-rows // 16),)
        for a, b in zip(both(), whole):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    @pytest.mark.parametrize("i", [0, 1, 2])
    @pytest.mark.parametrize("Tq", [1, 8])
    def test_kernel_layer_index_reads_that_layer(self, monkeypatch, Tq, i):
        """The kernel given the whole stacked pool and ``layer=i`` (a
        TRACED scalar, as the layer scan passes it) equals the
        single-layer call on ``pool[i]``."""
        monkeypatch.setattr(pa, "FORCE_INTERPRET", True)
        rng = np.random.RandomState(i)
        L, B, nH, Hkv, D, psz, P, max_pages = 3, 2, 4, 2, 64, 16, 9, 4
        q = jnp.asarray(rng.randn(B, Tq, nH, D), jnp.float32)
        kp = jnp.asarray(rng.randn(L, P, psz, Hkv * D), jnp.float32)
        vp = jnp.asarray(rng.randn(L, P, psz, Hkv * D), jnp.float32)
        pt = jnp.asarray(rng.permutation(np.arange(1, P))
                         .reshape(B, max_pages), jnp.int32)
        ctx = jnp.asarray([3, 40], jnp.int32)
        one = pa.ragged_paged_attention(q, kp[i], vp[i], pt, ctx)
        stacked = jax.jit(lambda lay: pa.ragged_paged_attention(
            q, kp, vp, pt, ctx, layer=lay))(jnp.int32(i))
        np.testing.assert_array_equal(np.asarray(stacked), np.asarray(one))

    @pytest.mark.parametrize("shape,layer", [
        ((9, 16, 2, 64), None),        # heads apart: [P, psz, Hkv, D]
        ((3, 9, 16, 128), None),       # a stacked pool and no layer
        ((9, 16, 128), 0),             # a layer and no stack
    ])
    def test_kernel_refuses_a_pool_it_would_have_to_reshape(self, shape,
                                                            layer):
        q = jnp.ones((2, 1, 4, 64), jnp.float32)
        kp = jnp.ones(shape, jnp.float32)
        with pytest.raises(ValueError, match="where it lies"):
            pa.ragged_paged_attention(
                q, kp, kp, jnp.zeros((2, 4), jnp.int32),
                jnp.zeros((2,), jnp.int32), layer=layer, interpret=True)


class TestOneOf:
    """``serving._one_of``: the segment loops' two-way branch."""

    @pytest.mark.parametrize("admits", [0, 2, 5])
    def test_equals_lax_cond_in_a_segment_shaped_loop(self, admits):
        from paddle_tpu.inference.serving import _one_of

        steps = 6

        def admit(st):
            return dict(st, pool=st["pool"].at[st["q"] % 4].add(1.0),
                        log=st["log"].at[st["step"]].set(st["q"]),
                        q=st["q"] + 1)

        def decode(st):
            return dict(st, pool=st["pool"] * 2.0,
                        log=st["log"].at[st["step"]].set(-1))

        def run(branch, n_real):
            def body(st):
                st = branch(st["q"] < n_real, admit, decode, st)
                return dict(st, step=st["step"] + 1)

            st = dict(pool=jnp.arange(4.0), log=jnp.zeros((steps,), jnp.int32),
                      q=jnp.int32(0), step=jnp.int32(0))
            return jax.lax.while_loop(
                lambda st: (st["step"] < steps) & (st["pool"][0] < 40.0),
                body, st)

        got = jax.jit(lambda n: run(_one_of, n))(jnp.int32(admits))
        want = jax.jit(lambda n: run(jax.lax.cond, n))(jnp.int32(admits))
        assert int(got["step"]) == int(want["step"]) > 0
        for n in want:
            np.testing.assert_array_equal(np.asarray(got[n]),
                                          np.asarray(want[n]), err_msg=n)


class TestUnifiedKernel:
    @pytest.mark.parametrize("nH,Hkv,D", [(4, 2, 64), (2, 2, 128),
                                          (8, 8, 64)])
    def test_mixed_phase_parity(self, nH, Hkv, D):
        """One launch serving co-resident prefill chunks (q_len > 1) and
        decode ticks (q_len == 1) over a SHUFFLED page table, vs the
        dense gather formulation."""
        rng = np.random.RandomState(0)
        B, Tq, psz, P, max_pages = 4, 8, 16, 33, 8
        q = jnp.asarray(rng.randn(B, Tq, nH, D), jnp.float32)
        kp = jnp.asarray(rng.randn(P, psz, Hkv * D), jnp.float32)
        vp = jnp.asarray(rng.randn(P, psz, Hkv * D), jnp.float32)
        pt = jnp.asarray(rng.permutation(np.arange(1, P))[:B * max_pages]
                         .reshape(B, max_pages), jnp.int32)
        ctx = jnp.asarray([0, 5, 37, 100], jnp.int32)
        qlen = jnp.asarray([1, 8, 3, 1], jnp.int32)
        out = pa.ragged_paged_attention(q, kp, vp, pt, ctx, qlen,
                                        interpret=True)
        cfg = llama.LlamaConfig.tiny(num_heads=nH, num_kv_heads=Hkv,
                                     hidden_size=nH * D)
        gk = kp[pt].reshape(B, max_pages * psz, Hkv, D)
        gv = vp[pt].reshape(B, max_pages * psz, Hkv, D)
        ref = llama._dense_cache_attention(
            cfg, q, gk, gv, ctx[:, None] + jnp.arange(Tq))
        for b in range(B):
            t = int(qlen[b])  # rows past q_len are padding (discarded)
            np.testing.assert_allclose(np.asarray(out)[b, :t],
                                       np.asarray(ref)[b, :t],
                                       rtol=2e-5, atol=2e-5)

    def test_pages_read_scale_with_position(self):
        """The analytic pages-fetched contract, which is the bound of
        the kernel's loop over a slot's pages: reads track ctx + q_len,
        not the table width, and a slot without a query row reads
        nothing."""
        assert pa.pages_read(0, 1, 16) == 1
        assert pa.pages_read(15, 1, 16) == 1
        assert pa.pages_read(16, 1, 16) == 2
        assert pa.pages_read(100, 1, 16) == 7
        assert pa.pages_read(32, 8, 16) == 3   # prefill chunk spans more
        assert pa.pages_read(0, 0, 16) == pa.pages_read(100, 0, 16) == 0
        np.testing.assert_array_equal(
            pa.pages_read(np.array([0, 16, 100]), np.array([1, 0, 8]), 16),
            [1, 0, 7])

    def test_dispatch_gates(self, monkeypatch):
        if jax.default_backend() == "cpu":
            assert not pa.paged_attention_active(16, 4, 2, 64)  # dense
        monkeypatch.setattr(pa, "FORCE_INTERPRET", True)
        assert pa.paged_attention_active(16, 4, 2, 64)
        assert not pa.paged_attention_active(12, 4, 2, 64)   # psz % 8
        assert not pa.paged_attention_active(16, 4, 2, 32)   # lanes < 128
        assert not pa.paged_attention_active(16, 3, 2, 64)   # GQA ragged
        import paddle_tpu

        paddle_tpu.set_flags({"use_paged_attention": False})
        try:
            assert not pa.paged_attention_active(16, 4, 2, 64)
        finally:
            paddle_tpu.set_flags({"use_paged_attention": True})

    def test_forward_with_pages_kernel_matches_fallback(self, monkeypatch):
        """llama.forward_with_pages with the kernel FORCED (interpret)
        vs the gather+dense fallback — one ragged decode tick on a
        shuffled page table, logits AND pool writes identical."""
        set_mesh(None)
        cfg = llama.LlamaConfig(
            vocab_size=128, hidden_size=256, intermediate_size=512,
            num_layers=1, num_heads=4, num_kv_heads=2, max_seq_len=128,
            dtype=jnp.float32, remat=False, scan_layers=False)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.RandomState(3)
        pool = llama.init_paged_pool(cfg, 17, 16)
        pool = {k: jnp.asarray(rng.randn(*v.shape), jnp.float32) * 0.1
                for k, v in pool.items()}
        pt = jnp.asarray(rng.permutation(np.arange(1, 17))
                         .reshape(2, 8), jnp.int32)
        toks = jnp.asarray([[3], [5]], jnp.int32)
        pos = jnp.asarray([9, 37], jnp.int32)
        ref_l, ref_pool = llama.forward_with_pages(params, toks, cfg,
                                                   pool, pt, pos)
        monkeypatch.setattr(pa, "FORCE_INTERPRET", True)
        pa.reset_selection_count()
        out_l, out_pool = llama.forward_with_pages(params, toks, cfg,
                                                   pool, pt, pos)
        assert pa.selection_count() >= 1
        np.testing.assert_allclose(np.asarray(out_l), np.asarray(ref_l),
                                   rtol=2e-4, atol=1e-5)
        for kk in ("k", "v"):
            np.testing.assert_allclose(np.asarray(out_pool[kk]),
                                       np.asarray(ref_pool[kk]),
                                       rtol=2e-5, atol=2e-5)

    def test_cpu_defaults_stay_dense(self):
        """Without the force, CPU dispatch must not select the paged
        kernel — tier-1 numerics ride the gather+dense path."""
        if jax.default_backend() != "cpu":
            pytest.skip("dispatch default differs on an accelerator")
        pa.reset_selection_count()
        cfg = llama.LlamaConfig.tiny(max_seq_len=64)
        params = llama.init_params(cfg)
        pool = llama.init_paged_pool(cfg, 9, 16)
        pt = jnp.asarray(np.arange(1, 9).reshape(2, 4), jnp.int32)
        llama.forward_with_pages(params, jnp.asarray([[1], [2]], jnp.int32),
                                 cfg, pool, pt,
                                 jnp.asarray([4, 9], jnp.int32))
        assert pa.selection_count() == 0


def _attend_reference(q, kp, vp, pt, ctx, qlen):
    """Plain float32 ``jax.numpy``: every slot's pages gathered through
    its table, keys [0, ctx + t] visible to row t, exact softmax. Rows
    past ``qlen`` are not the kernel's to get right; a slot with no row
    is zeros."""
    B, Tq, nH, D = q.shape
    psz, Hkv = kp.shape[1], kp.shape[2] // D
    f32 = jnp.float32
    gk = kp.astype(f32)[pt].reshape(B, -1, Hkv, D)
    gv = vp.astype(f32)[pt].reshape(B, -1, Hkv, D)
    qf = q.astype(f32).reshape(B, Tq, Hkv, nH // Hkv, D) / np.sqrt(D)
    s = jnp.einsum("bthrd,bkhd->bthrk", qf, gk, precision="highest")
    kpos = jnp.arange(gk.shape[1])
    qpos = ctx[:, None] + jnp.arange(Tq)
    s = jnp.where((kpos <= qpos[..., None])[:, :, None, None], s, -jnp.inf)
    out = jnp.einsum("bthrk,bkhd->bthrd", jax.nn.softmax(s, axis=-1), gv,
                     precision="highest").reshape(B, Tq, nH, D)
    return jnp.where((qlen > 0)[:, None, None, None], out, 0.0)


def _assert_live_rows_match(out, ref, qlen, tol=2e-5):
    out, ref = np.asarray(out, np.float32), np.asarray(ref)
    assert np.isfinite(out).all()
    for b, n in enumerate(np.asarray(qlen)):
        rows = slice(0, int(n)) if n else slice(None)   # free slot: zeros
        np.testing.assert_allclose(out[b, rows], ref[b, rows], rtol=tol,
                                   atol=tol, err_msg=f"slot {b}")


class TestFetchedPages:
    """The kernel copies by hand the pages a slot holds, ``_block_pages``
    a block, up to ``pages_read``: parity with the plain float32
    reference at every edge of a page, a block and the table."""

    nH, Hkv, D, psz, max_pages = 4, 2, 64, 16, 64

    def pool(self, rng, pages, dtype=jnp.float32, layers=None):
        shape = (pages, self.psz, self.Hkv * self.D)
        if layers:
            shape = (layers,) + shape
        return (jnp.asarray(rng.randn(*shape), dtype),
                jnp.asarray(rng.randn(*shape), dtype))

    def queries(self, rng, B, Tq, dtype=jnp.float32):
        return jnp.asarray(rng.randn(B, Tq, self.nH, self.D), dtype)

    def test_block_follows_the_shapes(self):
        """128 key rows a block, 256 under a small query block."""
        assert pa._block_pages(16, 64, 4096) == 8
        assert pa._block_pages(16, 64, 16) == pa._block_pages(
            16, 64, 1024) == 16
        assert pa._block_pages(8, 64, 4096) == 16
        assert pa._block_pages(32, 64, 4096) == 4
        assert pa._block_pages(16, 4, 16) == 4      # never past the table
        assert pa._block_pages(512, 64, 16) == 1

    @pytest.mark.parametrize("table", ["shuffled", "repeated"])
    @pytest.mark.parametrize("Tq", [1, 16, 64, 256])
    def test_edges_of_a_page_a_block_and_the_table(self, Tq, table):
        """One launch whose slots sit at context 0, 1, one short of /
        exactly / one past a page and a block of N pages, one whose
        table is full, one past the table's end with its padding rows,
        and a FREE slot between live ones — over a table of shuffled
        pages or one that names the same few pages again and again —
        with ragged chunk widths."""
        rng = np.random.RandomState(Tq)
        psz, mp = self.psz, self.max_pages
        blk = psz * pa._block_pages(psz, mp, self.nH * Tq)
        ctx = np.array([0, 1, psz - 1, psz, psz + 1, 0, blk - 1, blk,
                        blk + 1, 2 * blk - 1, mp * psz - Tq, mp * psz - 3])
        B = len(ctx)
        qlen = rng.randint(1, Tq + 1, size=B)
        qlen[:2] = (Tq, 1)
        qlen[5] = 0                                   # the free slot
        qlen[-2] = Tq                                 # the table, full
        qlen[-1] = min(Tq, 3)     # padding rows reach past the table
        P = 1 + B * mp
        if table == "shuffled":
            pt = rng.permutation(np.arange(1, P)).reshape(B, mp)
        else:
            pt = rng.randint(1, 6, size=(B, mp))
        q = self.queries(rng, B, Tq)
        kp, vp = self.pool(rng, P)
        args = [jnp.asarray(a, jnp.int32) for a in (pt, ctx, qlen)]
        out = pa.ragged_paged_attention(q, kp, vp, *args, interpret=True)
        _assert_live_rows_match(out, _attend_reference(q, kp, vp, *args),
                                qlen)

    @pytest.mark.parametrize("Tq", [1, 16])
    def test_pages_a_slot_does_not_hold_are_never_read(self, Tq):
        """Every page past a slot's ``pages_read`` is NaN, and so is
        every page nobody's table names: a copy of one would poison the
        output through ``0 * NaN``. A free slot's whole table is NaN."""
        rng = np.random.RandomState(7)
        psz, mp = self.psz, self.max_pages
        ctx = np.array([0, 5, 37, 127, 128, 500, 300])
        qlen = np.array([1, Tq, 1, Tq, 1, Tq, 0])
        B = len(ctx)
        P = 1 + B * mp
        pt = rng.permutation(np.arange(1, P)).reshape(B, mp)
        kp, vp = (np.array(a) for a in self.pool(rng, P))
        held = np.asarray(pa.pages_read(ctx, qlen, psz))
        assert held.tolist() == [(c + n - 1) // psz + 1 if n else 0
                                 for c, n in zip(ctx, qlen)]
        clean_k, clean_v = kp.copy(), vp.copy()
        kp[0] = vp[0] = np.nan
        for b in range(B):
            kp[pt[b, held[b]:]] = vp[pt[b, held[b]:]] = np.nan
        q = self.queries(rng, B, Tq)
        args = [jnp.asarray(a, jnp.int32) for a in (pt, ctx, qlen)]
        out = pa.ragged_paged_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                                        *args, interpret=True)
        ref = _attend_reference(q, jnp.asarray(clean_k),
                                jnp.asarray(clean_v), *args)
        _assert_live_rows_match(out, ref, qlen)

    @pytest.mark.parametrize("Tq", [1, 16, 64])
    def test_stacked_bf16_pool_with_a_traced_layer(self, Tq):
        """The call of the model's layer scan: the whole [L, P, psz,
        Hkv*D] bf16 pool, the layer a traced scalar, under jit."""
        rng = np.random.RandomState(3 + Tq)
        L, B, mp = 3, 5, self.max_pages
        P = 1 + B * mp
        pt = jnp.asarray(rng.permutation(np.arange(1, P)).reshape(B, mp),
                         jnp.int32)
        ctx = jnp.asarray([0, 130, 15, 700, 255], jnp.int32)
        qlen = jnp.asarray(rng.randint(0, Tq + 1, size=B), jnp.int32)
        q = self.queries(rng, B, Tq, jnp.bfloat16)
        kp, vp = self.pool(rng, P, jnp.bfloat16, layers=L)
        call = jax.jit(lambda lay: pa.ragged_paged_attention(
            q, kp, vp, pt, ctx, qlen, layer=lay, interpret=True))
        for lay in (0, 2):
            ref = _attend_reference(q, kp[lay], vp[lay], pt, ctx, qlen)
            # bf16 probabilities and output: 2^-8 of values of order 1
            _assert_live_rows_match(call(jnp.int32(lay)), ref, qlen,
                                    tol=2e-2)

    @staticmethod
    def kernel_equations(Tq, psz, max_pages, B=4):
        """Equations in the kernel's body, inner jaxprs (the rolled
        loops, ``pl.when`` branches) included: what every start traces
        and lowers, cache hit or not."""
        S = jax.ShapeDtypeStruct
        pool = S((3, 1 + B * max_pages, psz, 256), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(lambda *a: pa.ragged_paged_attention(
            *a, layer=jnp.int32(1)))(
                S((B, Tq, 4, 128), jnp.bfloat16), pool, pool,
                S((B, max_pages), jnp.int32), S((B,), jnp.int32),
                S((B,), jnp.int32))

        def count(jp):
            n = 0
            for eqn in jp.eqns:
                n += 1
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    n += count(sub)
            return n

        calls = [e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "pallas_call"]
        assert len(calls) == 1
        return count(calls[0].params["jaxpr"])

    @pytest.mark.parametrize("Tq", [1, 256])
    def test_traced_body_does_not_grow_with_the_table_or_the_block(self,
                                                                    Tq):
        """Set-up is traced and lowered at every start: the kernel's
        body is the same size whatever the table's width (16, 64, 128
        page slots) and whatever the block (4 to 32 pages), and small."""
        base = self.kernel_equations(Tq, 16, 64)
        assert base < 400
        for psz, max_pages in ((16, 16), (16, 128), (8, 64), (32, 64)):
            assert self.kernel_equations(Tq, psz, max_pages) == base, \
                (psz, max_pages)


class TestKernelThroughTheEngine:
    """The paged segment program with the kernel chosen (interpreted)
    against the same program on the gather path: admissions, decode
    ticks with retired and never-filled slots (``live`` -> ``q_len`` 0),
    re-admission into a slot that was left. ``tick``: the tick's fused
    kernels chosen as well, as on the chip: both arms of the segment
    program rope their rows in ``fused_rope_qk`` (``_rows_qkv``), the
    fallback's in XLA."""

    @pytest.mark.parametrize("tick", [False, True])
    @pytest.mark.parametrize("scan_layers", [True, False])
    def test_serves_the_fallbacks_tokens(self, monkeypatch, scan_layers,
                                         tick):
        from paddle_tpu.ops.pallas import tick_fusion

        set_mesh(None)
        tokens = {}
        for kernel in (False, True):
            # max_seq_len tells the two engines' cached programs apart
            cfg = llama.LlamaConfig(
                vocab_size=128, hidden_size=256, intermediate_size=512,
                num_layers=2, num_heads=4, num_kv_heads=2,
                max_seq_len=128 + kernel + 2 * tick, dtype=jnp.float32,
                remat=False, scan_layers=scan_layers)
            params = llama.init_params(cfg, jax.random.PRNGKey(0))
            monkeypatch.setattr(pa, "FORCE_INTERPRET", kernel)
            monkeypatch.setattr(tick_fusion, "FORCE_INTERPRET",
                                kernel and tick)
            pa.reset_selection_count()
            eng = ServingEngine(cfg, params, slots=3, max_len=96,
                                paged=True, page_size=8,
                                prompt_buckets=(16,))
            assert eng.paged_kernel_active() == kernel
            rng = np.random.RandomState(4)
            rids = [eng.add_request(
                rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32), g)
                for n, g in ((5, 9), (12, 3), (9, 14), (16, 6), (3, 11))]
            while eng._queue or eng.free_slot_count() < eng.slots:
                eng.run_segment(6)
            assert (pa.selection_count() > 0) == kernel
            done = eng.collect_finished()
            tokens[kernel] = [done[r] for r in rids]
        assert tokens[True] == tokens[False]


# ---------------------------------------------------------------------------
# paged engine: token-identical serving (acceptance criterion 3)
# ---------------------------------------------------------------------------


def _serve_r7_workload(cfg, params, prefix_cache=False, slots=3,
                       **paged_kw):
    """The r7 serving workload shape (mixed prompt/gen lengths through
    re-entrant segments with mid-flight arrivals)."""
    rng = np.random.RandomState(21)
    wave1 = [(rng.randint(0, cfg.vocab_size, (l,)).astype(np.int32), n)
             for l, n in [(5, 9), (12, 6), (8, 12)]]
    wave2 = [(rng.randint(0, cfg.vocab_size, (l,)).astype(np.int32), n)
             for l, n in [(20, 4), (3, 8), (15, 5), (7, 10)]]
    eng = ServingEngine(cfg, params, slots=slots, max_len=96,
                        prompt_buckets=(8, 16, 32), **paged_kw)
    pc = (PagedPrefixCache(eng.pager, capacity_pages=64)
          if prefix_cache else None)
    rids = [eng.add_request(p, n) for p, n in wave1]
    eng.run_segment(5, prefix_cache=pc)       # partial: slots still live
    rids += [eng.add_request(p, n) for p, n in wave2]
    while eng._queue or eng.free_slot_count() < eng.slots:
        eng.run_segment(7, prefix_cache=pc)
    out = eng.collect_finished()
    return eng, [out[r] for r in rids], wave1 + wave2


class TestPagedEngineParity:
    def test_r7_workload_token_identical_vs_generate(self, tiny):
        """Acceptance: the engine's greedy tokens == dense generate()'s,
        request by request, on the r7 mixed workload with mid-flight
        arrivals — and every page comes back."""
        cfg, params = tiny
        eng, out, reqs = _serve_r7_workload(cfg, params, page_size=16)
        for got, (p, n) in zip(out, reqs):
            assert got == _dense_reference(cfg, params, p, n)
        assert eng.pager.leak_report() == []

    def test_eos_freeze_and_slot_reuse(self, tiny):
        """EOS freezes a paged slot in-program, its pages free at the
        sync, and a queued request takes the slot within the same
        segment — token parity with the dense path's truncation."""
        cfg, params = tiny
        rng = np.random.RandomState(23)
        prompts = [rng.randint(0, cfg.vocab_size, (6 + i,)).astype(np.int32)
                   for i in range(4)]
        refs = [_dense_reference(cfg, params, p, 8) for p in prompts]
        eos = refs[0][1]                  # early EOS for request 0 only
        eng = ServingEngine(cfg, params, slots=1, max_len=96,
                            prompt_buckets=(16,), eos_token_id=eos,
                            paged=True, page_size=16)
        rids = [eng.add_request(p, 8) for p in prompts]
        while eng._queue or eng.free_slot_count() < eng.slots:
            eng.run_segment(24)
        out = eng.collect_finished()
        for rid, ref in zip(rids, refs):
            want = ref[:ref.index(eos) + 1] if eos in ref else ref
            assert out[rid] == want, (rid, out[rid], want)
        assert eng.pager.leak_report() == []

    def test_prefix_hit_is_ref_bump_only(self, tiny):
        """Acceptance: a prefix hit performs ZERO KV row copies — pages
        are shared by refcount (cow_shares moves, cow_breaks stays 0)
        and the hit path is token-identical to cold."""
        from paddle_tpu.observability import metrics

        cfg, params = tiny
        rng = np.random.RandomState(41)
        prefix = rng.randint(0, cfg.vocab_size, (32,)).astype(np.int32)
        tails = [rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
                 for _ in range(4)]
        prompts = [np.concatenate([prefix, t]) for t in tails]
        refs = [_dense_reference(cfg, params, p, 6) for p in prompts]

        def serve(with_cache):
            eng = ServingEngine(cfg, params, slots=2, max_len=96,
                                prompt_buckets=(8, 16, 64), paged=True,
                                page_size=16)
            pc = (PagedPrefixCache(eng.pager, capacity_pages=64)
                  if with_cache else None)
            rids = [eng.add_request(p, 6) for p in prompts]
            while eng._queue or eng.free_slot_count() < eng.slots:
                eng.run_segment(16, prefix_cache=pc)
            done = eng.collect_finished()
            return eng, pc, [done[r] for r in rids]

        _, _, cold = serve(False)
        shares0 = metrics.counter("serving.pages.cow_shares").value
        breaks0 = metrics.counter("serving.pages.cow_breaks").value
        eng, pc, hot = serve(True)
        assert cold == hot == refs
        assert pc.hits >= 2 and pc.hit_tokens >= 2 * 32
        assert metrics.counter("serving.pages.cow_shares").value > shares0
        assert metrics.counter("serving.pages.cow_breaks").value == breaks0
        assert eng.pager.cow_breaks == 0
        # dedup, not copy: the cache's entry pages ARE slot pages that
        # were live — clearing the cache returns everything
        pc.clear()
        assert eng.pager.leak_report() == []


# ---------------------------------------------------------------------------
# pages-free admission: the max_len wall, backpressure, eviction valve
# ---------------------------------------------------------------------------


class TestPagesFreeAdmission:
    def test_max_len_wall_removed(self, tiny):
        """Acceptance: a pool provisioned WELL below slots x max_len
        serves a workload at full slot concurrency — per-slot footprint
        is live pages, not the worst-case window. 4 slots x max_len 96
        would need 384 contiguous rows; the pool holds 208."""
        cfg, params = tiny
        eng = ServingEngine(cfg, params, slots=4, max_len=96,
                            prompt_buckets=(8, 16, 32), paged=True,
                            page_size=16, num_pages=14)   # 13*16 = 208
        assert (eng.pager.num_pages - 1) * eng.page_size \
            < eng.slots * eng.max_len
        rng = np.random.RandomState(7)
        reqs = [(rng.randint(0, cfg.vocab_size, (l,)).astype(np.int32), n)
                for l, n in [(30, 9), (5, 7), (12, 3), (3, 12), (17, 5),
                             (25, 4), (8, 8), (6, 6)]]
        rids = [eng.add_request(p, n) for p, n in reqs]
        peak_live = 0
        while eng._queue or eng.free_slot_count() < eng.slots:
            eng.run_segment(8)
            peak_live = max(peak_live,
                            eng.slots - eng.free_slot_count())
        out = eng.collect_finished()
        for rid, (p, n) in zip(rids, reqs):
            assert out[rid] == _dense_reference(cfg, params, p, n)
        assert peak_live == eng.slots    # full concurrency, 54% the HBM
        assert eng.pager.leak_report() == []

    def test_backpressure_pages_counted(self, tiny):
        """Satellite 2: admission defers on pages-free (NOT slots-free)
        and counts backpressure{reason='pages'}; deferred requests serve
        once pages retire. FCFS order preserved."""
        from paddle_tpu.observability import metrics

        cfg, params = tiny
        # 5 usable pages; each request spans 3 -> only one admits at a
        # time even though TWO slots are free
        eng = ServingEngine(cfg, params, slots=2, max_len=96,
                            prompt_buckets=(32,), paged=True,
                            page_size=16, num_pages=6)
        rng = np.random.RandomState(5)
        reqs = [(rng.randint(0, cfg.vocab_size, (30,)).astype(np.int32), 9)
                for _ in range(3)]
        rids = [eng.add_request(p, n) for p, n in reqs]
        before = metrics.counter("serving.backpressure_pages").value
        while eng._queue or eng.free_slot_count() < eng.slots:
            eng.run_segment(16)
        out = eng.collect_finished()
        assert eng.page_backpressure_events > 0
        assert metrics.counter("serving.backpressure_pages").value > before
        for rid, (p, n) in zip(rids, reqs):
            assert out[rid] == _dense_reference(cfg, params, p, n)
        assert eng.pager.leak_report() == []

    def test_prefix_cache_yields_pages_under_pressure(self, tiny):
        """The eviction valve: cached history releases LRU pages before
        live traffic defers — cache-held pages never starve admission."""
        cfg, params = tiny
        # 5 usable pages; each request spans 4 and leaves a 3-page
        # prefix entry behind — the next admission MUST reclaim it
        eng = ServingEngine(cfg, params, slots=2, max_len=96,
                            prompt_buckets=(64,), paged=True,
                            page_size=16, num_pages=6)
        pc = PagedPrefixCache(eng.pager, capacity_pages=8)
        rng = np.random.RandomState(11)
        reqs = [(rng.randint(0, cfg.vocab_size, (50,)).astype(np.int32), 6)
                for _ in range(3)]
        rids = [eng.add_request(p, n) for p, n in reqs]
        while eng._queue or eng.free_slot_count() < eng.slots:
            eng.run_segment(16, prefix_cache=pc)
        out = eng.collect_finished()
        for rid, (p, n) in zip(rids, reqs):
            assert out[rid] == _dense_reference(cfg, params, p, n)
        assert pc.evictions > 0          # the valve actually opened
        pc.clear()
        assert eng.pager.leak_report() == []


# ---------------------------------------------------------------------------
# paged prefix cache unit behaviour
# ---------------------------------------------------------------------------


class TestPagedPrefixCacheUnit:
    def test_match_insert_evict_mechanics(self, tiny):
        cfg, _ = tiny
        pgr = PagedKVCache(cfg, slots=1, page_size=8, num_pages=17,
                           max_pages=8)
        pc = PagedPrefixCache(pgr, capacity_pages=4)
        rng = np.random.RandomState(43)
        base = rng.randint(0, cfg.vocab_size, (32,)).astype(np.int32)
        pages, _ = pgr.reserve(32)               # a "slot" holding base
        pc.insert(base, pages)
        assert pc.pages_held == 4
        assert all(pgr.allocator.ref(p) == 2 for p in pages)
        # partial overlap: same first 8 tokens -> one-page hit, strict
        probe = np.concatenate(
            [base[:8], rng.randint(0, cfg.vocab_size, (12,))]
        ).astype(np.int32)
        m = pc.match(probe)
        assert m is not None and m.length == 8 and len(m.pages) == 1
        assert m.pages[0] == pages[0]
        # whole-prompt coverage is refused (one token must prefill)
        assert pc.match(base[:8]) is None
        # capacity eviction: a second entry pushes past 4 pages
        other_pages, _ = pgr.reserve(32)
        pc.insert(rng.randint(0, cfg.vocab_size, (32,)).astype(np.int32),
                  other_pages)
        assert pc.pages_held <= 4 and pc.evictions >= 1
        pgr.release_pages(pages)
        pgr.release_pages(other_pages)
        pc.clear()
        assert pgr.leak_report() == []

    def test_contiguous_cache_is_refused_by_name(self, tiny):
        """``paged`` survives as a keyword (the benchmark's config files
        pass it) that can only be true."""
        cfg, params = tiny
        with pytest.raises(ValueError, match="contiguous KV cache is gone"):
            ServingEngine(cfg, params, slots=1, max_len=96,
                          prompt_buckets=(16,), paged=False)
        eng = ServingEngine(cfg, params, slots=1, max_len=96,
                            prompt_buckets=(16,), paged=True)
        assert not hasattr(eng, "paged") and not hasattr(eng, "_cache")

    def test_partial_overlap_hit_and_eviction_through_admission(self, tiny):
        """A page-aligned PARTIAL overlap (same first 16 of a cached 32
        tokens) admits through the hit, a cache of two pages evicts as
        new prompts arrive, and every request's tokens still equal the
        dense path's."""
        cfg, params = tiny
        rng = np.random.RandomState(43)
        base = rng.randint(0, cfg.vocab_size, (38,)).astype(np.int32)
        probe = np.concatenate(
            [base[:16], rng.randint(0, cfg.vocab_size, (20,))]
        ).astype(np.int32)
        other = rng.randint(0, cfg.vocab_size, (38,)).astype(np.int32)
        eng = ServingEngine(cfg, params, slots=1, max_len=96,
                            prompt_buckets=(8, 16, 64), page_size=16)
        pc = PagedPrefixCache(eng.pager, capacity_pages=2)
        hits = []
        for prompt in (base, probe, other, base):
            rid = eng.add_request(prompt, 4)
            while eng._queue or eng.free_slot_count() < eng.slots:
                eng.run_segment(8, prefix_cache=pc)
            r = eng._finished[-1]
            hits.append(r.prefix_hit_len)
            assert eng.collect_finished()[rid] == _dense_reference(
                cfg, params, prompt, 4)
            assert pc.pages_held <= 2
        # base cold; probe shares base's first page; other cold; by the
        # time base returns its entry has been evicted for other's
        assert hits == [0, 16, 0, 0], hits
        assert pc.evictions >= 2
        pc.clear()
        assert eng.pager.leak_report() == []

    def test_harvested_pages_match_standalone_prefill(self, tiny):
        """Cache plumbing parity: the pages harvested from a serving slot
        after admission hold the rows ``llama.prompt_kv``'s standalone
        prefill computes."""
        cfg, params = tiny
        rng = np.random.RandomState(45)
        prompt = rng.randint(0, cfg.vocab_size, (16,)).astype(np.int32)
        eng = ServingEngine(cfg, params, slots=1, max_len=96,
                            prompt_buckets=(16,), page_size=8)
        pc = PagedPrefixCache(eng.pager, capacity_pages=8)
        eng.add_request(prompt, 2)
        while eng._queue or eng.free_slot_count() < eng.slots:
            eng.run_segment(8, prefix_cache=pc)
        m = pc.match(np.concatenate([prompt, prompt[:4]]))
        assert m is not None and m.length == 16 and len(m.pages) == 2
        cache, _ = llama.prompt_kv(params, prompt, cfg)
        L = cfg.num_layers
        for plane in ("k", "v"):
            rows = np.asarray(eng.pager.pool[plane])[:, m.pages].reshape(
                L, 16, cfg.num_kv_heads, cfg.head_dim)
            np.testing.assert_allclose(
                rows, np.asarray(cache[plane][:, 0, :16]),
                rtol=1e-5, atol=1e-6)
        pc.clear()
        assert eng.pager.leak_report() == []


# ---------------------------------------------------------------------------
# audit: the one-sync-per-segment invariant survives paging
# ---------------------------------------------------------------------------


class TestPreemptFailoverLeakGuard:
    def test_randomized_preempt_resume_kill_schedule(self, tiny):
        """r13 satellite: after ANY preempt / requeue / failover cycle
        the pool must return to the free-list invariant. A seeded random
        schedule interleaves admissions, serving segments, priority
        preemptions (with and without prefix-cache parking), and
        full-engine aborts (the failover teardown); the allocator
        invariant holds at every step and everything drains clean.

        r19 (ISSUE 14 satellite): the cache carries a HOST TIER, and
        the schedule gains forced spill passes (``evict_until`` over
        the whole pool) — staging rides the segments the schedule
        already runs, restores happen on whatever hits follow, and the
        leak audit must stay clean through arbitrary interleavings of
        spill/restore with preempt/abort."""
        from paddle_tpu.inference.kv_tiers import HostTier

        cfg, params = tiny
        eng = ServingEngine(cfg, params, slots=2, max_len=96,
                            prompt_buckets=(8, 16, 32), paged=True,
                            page_size=16, chunked_prefill=True,
                            prefill_chunks=(8,))
        pc = PagedPrefixCache(eng.pager, capacity_pages=16,
                              host_tier=HostTier(eng.pager,
                                                 capacity_pages=32))
        rng = np.random.RandomState(3)
        for step in range(40):
            op = rng.randint(5)
            if op == 0 and len(eng._queue) < 4:          # admit
                eng.add_request(
                    rng.randint(0, cfg.vocab_size,
                                (int(rng.randint(4, 20)),)).astype(
                                    np.int32),
                    int(rng.randint(2, 10)))
            elif op == 1 and (eng._queue
                              or eng.free_slot_count() < eng.slots):
                eng.run_segment(16, prefix_cache=pc)     # serve a bit
            elif op == 2:                                # preempt+requeue
                live = [s for s in range(eng.slots)
                        if eng._active[s] is not None
                        and eng.can_preempt(s)]
                if live:
                    s = live[int(rng.randint(len(live)))]
                    park = pc if rng.randint(2) else None
                    r = eng.preempt_slot(s, prefix_cache=park)
                    eng._queue.insert(0, r)
                else:
                    continue
            elif op == 3 and rng.rand() < 0.15:          # replica kill
                orphans = eng.abort()
                pc.reset()                               # failover path
                for r in orphans:                        # requeue all
                    eng._queue.append(r)
            elif op == 4:                                # forced spill
                pc.evict_until(eng.pager.num_pages)
            assert eng.pager.allocator.check() == [], \
                f"allocator invariant broke at step {step}"
        while eng._queue or eng.free_slot_count() < eng.slots:
            eng.run_segment(16, prefix_cache=pc)
        for r in eng._finished:
            assert r.done
        # r19: the spill/restore cycles above must leave the pool
        # accountable — cache-held pages reconcile and clear drains all
        pc.clear()
        assert eng.pager.leak_report() == []
        assert pc.host_tier.stats()["pending_stages"] == 0

    def test_randomized_cross_pool_handoff_schedule(self, tiny):
        """r22 (ISSUE 17 satellite): the randomized schedule gains the
        disaggregation ops — export-after-prefill on a source pool and
        import-before-decode on a destination pool, interleaved with
        the existing admit / serve / preempt / spill churn. TWO
        engines stand in for the prefill and decode pools, each with
        its own allocator and host-tiered cache; requests that have
        emitted a first token get preempted, their prefix staged,
        exported as host bytes, imported into the other pool's cache
        and requeued there (the DisaggRouter's handoff path, driven
        adversarially). The free-list/refcount invariant must hold on
        BOTH pools at every step, and both pools drain clean."""
        from paddle_tpu.inference.kv_tiers import HostTier

        cfg, params = tiny

        def mk():
            eng = ServingEngine(cfg, params, slots=2, max_len=96,
                                prompt_buckets=(8, 16, 32), paged=True,
                                page_size=16, chunked_prefill=True,
                                prefill_chunks=(8,))
            pc = PagedPrefixCache(eng.pager, capacity_pages=16,
                                  host_tier=HostTier(eng.pager,
                                                     capacity_pages=32))
            return eng, pc

        src, pc_src = mk()          # the prefill pool
        dst, pc_dst = mk()          # the decode pool
        rng = np.random.RandomState(17)
        handoffs = 0
        for step in range(48):
            op = rng.randint(6)
            if op == 0 and len(src._queue) < 4:          # admit @ prefill
                # generations long enough to SURVIVE a segment — a
                # request must be mid-decode for a handoff to exist
                src.add_request(
                    rng.randint(0, cfg.vocab_size,
                                (int(rng.randint(4, 20)),)).astype(
                                    np.int32),
                    int(rng.randint(12, 24)))
            elif op == 1 and (src._queue
                              or src.free_slot_count() < src.slots):
                src.run_segment(8, prefix_cache=pc_src)
            elif op == 2 and (dst._queue
                              or dst.free_slot_count() < dst.slots):
                dst.run_segment(8, prefix_cache=pc_dst)
            elif op == 3:                                # handoff
                live = [s for s in range(src.slots)
                        if src._active[s] is not None
                        and src.can_preempt(s)
                        and src._active[s].tokens
                        and not src._active[s].done]
                if not live:
                    continue
                s = live[int(rng.randint(len(live)))]
                r = src.preempt_slot(s, prefix_cache=pc_src)
                if pc_src.host_tier.stats()["pending_stages"]:
                    pc_src.host_tier.flush()             # export side
                fp, _ = r.resume_view()
                plen_b = pc_src.round_down(len(fp))
                if plen_b:
                    key = np.asarray(fp[:plen_b], np.int32).tobytes()
                    exp = pc_src.export_host(key)
                    if exp is not None:                  # import side
                        planes = {p: exp[p] for p in exp
                                  if p not in ("tokens", "pages")}
                        pc_dst.import_host(exp["tokens"], planes)
                r.rid = dst._next_rid                    # requeue @ decode
                dst._next_rid += 1
                dst._queue.append(r)
                handoffs += 1
            elif op == 4 and rng.rand() < 0.3:           # forced spill
                (pc_src if rng.randint(2) else pc_dst).evict_until(
                    src.pager.num_pages)
            elif op == 5 and rng.rand() < 0.1:           # decode-pool kill
                for r in dst.abort():
                    dst._queue.append(r)
                pc_dst.reset()
            for eng, who in ((src, "prefill"), (dst, "decode")):
                assert eng.pager.allocator.check() == [], \
                    f"{who} allocator invariant broke at step {step}"
        assert handoffs > 0, "schedule never exercised a handoff"
        # clean drain of BOTH pools
        for eng, pc in ((src, pc_src), (dst, pc_dst)):
            while eng._queue or eng.free_slot_count() < eng.slots:
                eng.run_segment(16, prefix_cache=pc)
            for r in eng._finished:
                assert r.done
            pc.clear()
            assert eng.pager.leak_report() == []
            assert pc.host_tier.stats()["pending_stages"] == 0


class TestPagedSchedulerAudit:
    def test_online_serve_loop_syncs(self, tiny):
        """The paged serve loop keeps the r7/r9 contract: exactly ONE
        allowed device->host sync per segment (the event fetch), zero
        flagged — page-table bookkeeping is pure host arithmetic."""
        from paddle_tpu.analysis import syncs
        from paddle_tpu.inference.scheduler import (OnlineScheduler,
                                                    staggered_arrivals)

        cfg, params = tiny
        eng = ServingEngine(cfg, params, slots=4, max_len=96, chunk=8,
                            prompt_buckets=(16,), paged=True, page_size=16)
        pc = PagedPrefixCache(eng.pager, capacity_pages=16)
        sched = OnlineScheduler(eng, seg_steps=16, prefix_cache=pc)
        arrivals = staggered_arrivals(0, 6, 0.01, cfg.vocab_size,
                                      prompt_lens=(8, 12), gen_lens=(4, 6))
        sched.serve(arrivals)          # warm: compiles + first fetches
        eng.reset_slots()
        pc.clear()
        sched._reqs.clear()
        with syncs.SyncAudit() as sa:
            sa.phase = "replay"
            report = sched.serve(arrivals)
        assert report.n_requests == 6
        flagged = sa.flagged("replay")
        assert flagged == [], [f"{e.kind}@{e.site}" for e in flagged]
        allowed = sa.allowed("replay")
        assert set(allowed) == {"serving.segment_event_fetch"}
        assert allowed["serving.segment_event_fetch"] == report.segments
        assert report.pages is not None and report.backpressure_pages == 0

    def test_paged_cache_keys_bucketed(self, tiny):
        """Page tables must be DATA, not shape: repeated paged segments
        (prefix on and off) grow no unbucketed program keys."""
        from paddle_tpu.analysis import recompile

        cfg, params = tiny
        eng = ServingEngine(cfg, params, slots=4, max_len=96, chunk=8,
                            prompt_buckets=(16,), paged=True, page_size=16)
        pc = PagedPrefixCache(eng.pager, capacity_pages=16)
        for _ in range(2):
            eng.add_request(np.arange(8, dtype=np.int32) % cfg.vocab_size,
                            3)
            eng.run_segment(8, prefix_cache=pc)
        lint = recompile.lint_cache_keys(**eng.cache_info())
        assert not lint.hazard
        pc.clear()
        assert eng.pager.leak_report() == []
