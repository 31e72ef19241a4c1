"""The latent-attention sparse-expert decoder (``models/latent_moe.py``)
against its plain reference (``tests/reference_latent_moe.py``), at a small
size on the CPU: (a) prefill then decode through the paged latent cache,
(b) the two kernels in interpret mode against the fallbacks, (c) the share
test, (d) the router and planted faults, (e) the multi-token-prediction
module, (f) the engine end to end, (g) the refusals, (h) the counters with
and without a trace.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_latent_moe as ref
from paddle_tpu.inference.scheduler import Arrival, OnlineScheduler
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import family_of, latent_moe as lm, llama
from paddle_tpu.ops.pallas import grouped_matmul, mla_attention
from paddle_tpu.parallel import set_mesh
from paddle_tpu.profiler import _hooks

PSZ = 8
SHARE = (4, 4)           # this chip holds experts 4..7 of 16


def sizes(cfg):
    """The config as the public config.json's keys (what the reference
    reads)."""
    return {"num_attention_heads": cfg.num_heads,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "kv_lora_rank": cfg.kv_lora_rank,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta}


NORMS = {"n1", "n2", "n3", "n4", "nq", "nkv", "nh", "ne", "nm", "ln_f"}


def jiggle(params, seed=3):
    """Norm scales away from 1, so that a dropped or misplaced norm
    shows."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    rng = np.random.RandomState(seed)
    out = []
    for path, a in leaves:
        if path[-1].key in NORMS:
            a = a * (1.0 + 0.3 * rng.standard_normal(a.shape)).astype(
                a.dtype)
        out.append(a)
    return jax.tree_util.tree_unflatten(tree, out)


_JITS = {}


def ref_logits(params, tokens, m, held, pad_to=24):
    """``ref.logits`` under one jit a (routing function, share): the
    sequence is padded to ``pad_to`` (causal: what follows a position
    changes nothing before it)."""
    key = (ref.route, ref.layer, held, pad_to)
    if key not in _JITS:
        _JITS[key] = jax.jit(lambda p, t: ref.logits(p, t, m, held))
    seq = np.zeros((pad_to,), np.int32)
    seq[:len(tokens)] = np.asarray(tokens)
    return np.asarray(_JITS[key](params, jnp.asarray(seq)))[:len(tokens)]


@pytest.fixture(scope="module")
def tiny():
    set_mesh(None)
    cfg = lm.LatentMoEConfig.tiny(held_experts=SHARE)
    params = jiggle(lm.init_params(cfg, jax.random.PRNGKey(1)))
    return cfg, params


@contextlib.contextmanager
def kernels_interpreted():
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mla_attention, "FORCE_INTERPRET", True)
        mp.setattr(grouped_matmul, "FORCE_INTERPRET", True)
        yield


def paged_run(cfg, params, prompts, n_decode, dead=()):
    """Admit ``prompts`` one a slot (padded to 16, as the engine's admit
    branch does), then ``n_decode`` ticks over all slots teacher-forced
    with the reference's tokens; slots in ``dead`` stop after the
    admission. Returns {slot: [logits at each fed position]} and the
    tokens fed."""
    B, width = len(prompts), 16
    max_pages = 6
    # a jit of its own a call: the kernels' dispatch is read while tracing
    def forward(tokens, pool, table, pos, live=None, logit_pos=None,
                counters=False):
        return lm.forward_with_pages(params, tokens, cfg, pool, table, pos,
                                     live=live, logit_pos=logit_pos,
                                     with_counters=counters)

    forward = jax.jit(forward, static_argnames=("counters",))
    pool = lm.init_paged_pool(cfg, 1 + B * max_pages, PSZ)
    table = 1 + np.arange(B * max_pages, dtype=np.int32).reshape(B, max_pages)
    m = sizes(cfg)
    full = [np.concatenate([p, np.zeros(n_decode, np.int32)])
            for p in prompts]
    got = {b: [] for b in range(B)}
    for b, p in enumerate(prompts):
        row = np.zeros((1, width), np.int32)
        row[0, :len(p)] = p
        logits, pool = forward(
            jnp.asarray(row), pool, jnp.asarray(table[b:b + 1]),
            jnp.zeros((1,), jnp.int32), logit_pos=jnp.int32(len(p) - 1))
        got[b].append(np.asarray(logits[0]))
        full[b][len(p)] = int(np.argmax(logits[0]))
    pos = np.array([len(p) for p in prompts], np.int32)
    live = np.array([b not in dead for b in range(B)])
    for _ in range(n_decode - 1):
        nxt = np.array([full[b][pos[b]] for b in range(B)], np.int32)
        logits, pool, cnt = forward(
            jnp.asarray(nxt[:, None]), pool, jnp.asarray(table),
            jnp.asarray(pos), live=jnp.asarray(live), counters=True)
        assert int(cnt[0]) == cfg.num_experts_per_tok \
            * cfg.num_expert_layers * int(live.sum())
        for b in range(B):
            if live[b]:
                got[b].append(np.asarray(logits[b]))
                full[b][pos[b] + 1] = int(np.argmax(logits[b]))
                pos[b] += 1
    return got, full, m


PROMPTS = [np.array([3, 9, 200, 17, 5], np.int32),
           np.arange(40, 56, dtype=np.int32),
           np.array([7, 7, 7, 90, 14, 250, 1, 33, 2], np.int32)]


def check_against_reference(cfg, params, got, full, m):
    for b, rows in got.items():
        n0 = len(PROMPTS[b])
        want = ref_logits(params, full[b], m, cfg.experts)
        for i, lg in enumerate(rows):
            np.testing.assert_allclose(lg, want[n0 - 1 + i], rtol=2e-3,
                                       atol=2e-3)


# (a) ----------------------------------------------------------------------

def test_prefill_then_decode_through_pages_matches_reference(tiny):
    cfg, params = tiny
    got, full, m = paged_run(cfg, params, PROMPTS, 6, dead=(1,))
    assert len(got[1]) == 1 and len(got[0]) == 6
    check_against_reference(cfg, params, got, full, m)


# (b) ----------------------------------------------------------------------

def test_kernels_interpreted_match_reference_and_fallback(tiny):
    cfg, params = tiny
    plain, _, _ = paged_run(cfg, params, PROMPTS, 4, dead=(1,))
    n_mla = mla_attention.selection_count()
    n_gmm = grouped_matmul.selection_count()
    with kernels_interpreted():
        assert lm.paged_kernel_active(cfg, PSZ)
        got, full, m = paged_run(cfg, params, PROMPTS, 4, dead=(1,))
    assert mla_attention.selection_count() > n_mla
    assert grouped_matmul.selection_count() > n_gmm
    check_against_reference(cfg, params, got, full, m)
    for b in got:
        for a, c in zip(got[b], plain[b]):
            np.testing.assert_allclose(a, c, rtol=2e-3, atol=2e-3)


def mla_dense(q, plane, table, ctx):
    """``o_lat`` of every query row at its own position, float64: the
    slot's pages gathered, one softmax over the whole table."""
    q, plane = np.asarray(q, np.float64), np.asarray(plane, np.float64)
    B, Tq = q.shape[:2]
    rows = plane[np.asarray(table)].reshape(B, -1, plane.shape[-1])
    s = np.einsum("bthc,bwc->bthw", q, rows)
    posn = np.asarray(ctx)[:, None] + np.arange(Tq)[None]
    seen = np.arange(rows.shape[1])[None, None] <= posn[:, :, None]
    s = np.where(seen[:, :, None, :], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return p / p.sum(-1, keepdims=True), rows


def assert_mla_rows(out, want, qlen, TB, tol=1e-4):
    """The kernel's contract a slot: live rows are the reference's, the
    padding rows of a live block are finite, blocks past ``q_len`` zeros."""
    out = np.asarray(out, np.float32)
    for b, n in enumerate(np.asarray(qlen)):
        n, whole = int(n), -(-int(n) // TB) * TB
        np.testing.assert_allclose(out[b, :n], want[b, :n], rtol=tol,
                                   atol=tol)
        assert np.isfinite(out[b, n:whole]).all()
        assert not out[b, whole:].any()


def test_mla_kernel_mixed_chunks_against_dense():
    """Decode ticks and chunks in one launch; a slot whose chunk is all
    padding past a block gets zeros there."""
    rng = np.random.RandomState(0)
    B, Tq, nH, W, R, psz, max_pages = 3, 128, 4, 256, 128, 8, 24
    P = 1 + B * max_pages
    pool = jnp.asarray(rng.standard_normal((2, P, psz, W)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, Tq, nH, W)) * 0.1, jnp.float32)
    table = 1 + rng.permutation(B * max_pages).astype(np.int32).reshape(
        B, max_pages)
    ctx = np.array([0, 37, 5], np.int32)
    qlen = np.array([128, 1, 40], np.int32)
    out = mla_attention.mla_paged_attention(
        q, pool, jnp.asarray(table), jnp.asarray(ctx), jnp.asarray(qlen),
        layer=jnp.int32(1), rank=R, interpret=True)
    p, rows = mla_dense(q, pool[1], table, ctx)
    want = np.einsum("bthw,bwr->bthr", p, rows[..., :R])
    assert_mla_rows(out, want, qlen, mla_attention.QUERY_ROWS // nH)


class TestFetchedPages:
    """``mla_paged_attention`` copies by hand the pages a query block can
    see, ``_block_pages`` a block: parity with the dense reference at
    every edge of a page, a block and the table, with every other page
    poisoned (``tests/test_paged_kv.py::TestFetchedPages``' twin)."""

    nH, W, R, psz, max_pages = 8, 256, 128, 8, 40

    @staticmethod
    def pages_needed(ctx, qlen, block, TB, psz, max_pages):
        """Pages query block ``block`` of a slot can see: up to the page
        of its last LIVE position, inside the table; none past ``qlen``."""
        end = ctx + np.minimum((block + 1) * TB, qlen) - 1
        return (np.minimum(end // psz, max_pages - 1) + 1) \
            * (block * TB < qlen)

    def test_block_follows_the_shapes(self):
        """512 key rows a block under a decode tick's 128 query rows, 256
        under an admission's 512, never more than the table names."""
        assert mla_attention._block_pages(16, 96, 128) == 32
        assert mla_attention._block_pages(16, 96, 512) == 16
        assert mla_attention._block_pages(8, 96, 128) == 64
        assert mla_attention._block_pages(16, 6, 128) == 6
        assert mla_attention._block_pages(1024, 6, 128) == 1

    @pytest.mark.parametrize("Tq", [1, 64, 256])
    def test_pages_past_a_blocks_need_are_never_read(self, Tq):
        """Every page a slot's LAST live query block need not see is NaN,
        the trash page and the pages nobody names too: a copy of one
        would poison the output through ``0 * NaN``. Slots with ``q_len``
        0, ``q_len`` < Tq, a context that ends on a page's edge, on a
        block's edge and at the table's end, one launch."""
        rng = np.random.RandomState(Tq)
        nH, W, R, psz, mp = self.nH, self.W, self.R, self.psz, self.max_pages
        TB = max(1, min(Tq, mla_attention.QUERY_ROWS // nH))
        blk = psz * mla_attention._block_pages(psz, mp, TB * nH)
        # (context, live rows): the chunk's last live position is ...
        qlen = np.array([Tq, 0, 1, Tq, max(Tq // 2, 1), Tq, Tq,
                         min(Tq, 3), Tq])
        end = np.array([0, 50, psz - 1,          # one short of a page edge
                        psz, 3 * psz,            # on a page edge
                        blk - 1, blk,            # round a block edge
                        mp * psz - 1,            # the table's end, padding
                        mp * psz - 1])           # rows past it; and full
        ctx = np.maximum(end - (qlen - 1), 0)
        B = len(ctx)
        P = 1 + B * mp + 3
        table = 1 + rng.permutation(B * mp).reshape(B, mp)
        plane = rng.standard_normal((P, psz, W)).astype(np.float32)
        q = jnp.asarray(rng.standard_normal((B, Tq, nH, W)) * 0.1,
                        jnp.float32)
        p, rows = mla_dense(q, plane, table, ctx)
        want = np.einsum("bthw,bwr->bthr", p, rows[..., :R])
        poisoned = plane.copy()
        poisoned[0] = poisoned[1 + B * mp:] = np.nan
        last = np.maximum(-(-qlen // TB) - 1, 0)
        held = self.pages_needed(ctx, qlen, last, TB, psz, mp)
        for b in range(B):
            poisoned[table[b, held[b]:]] = np.nan
        assert held[1] == 0 and held[-1] == mp and np.isnan(
            poisoned[table[1]]).all()
        out = mla_attention.mla_paged_attention(
            q, jnp.asarray(poisoned)[None], jnp.asarray(table, jnp.int32),
            jnp.asarray(ctx, jnp.int32), jnp.asarray(qlen, jnp.int32),
            rank=R, interpret=True)
        assert_mla_rows(out, want, qlen, TB)

    def test_each_query_block_fetches_only_its_own_pages(self):
        """A chunk of several query blocks: block ``k`` is computed from a
        pool whose pages past block ``k``'s need are NaN (one slot, so
        nothing that ran before it saw them) — its live rows still match:
        no block reads what only a later block of its slot may see."""
        rng = np.random.RandomState(5)
        nH, W, R, psz, mp = self.nH, self.W, self.R, self.psz, self.max_pages
        Tq, TB = 256, mla_attention.QUERY_ROWS // self.nH
        ctx, qlen = np.array([13]), np.array([200])
        table = 1 + rng.permutation(mp).reshape(1, mp)
        plane = rng.standard_normal((1 + mp, psz, W)).astype(np.float32)
        q = jnp.asarray(rng.standard_normal((1, Tq, nH, W)) * 0.1,
                        jnp.float32)
        p, rows = mla_dense(q, plane, table, ctx)
        want = np.einsum("bthw,bwr->bthr", p, rows[..., :R])
        for k in range(-(-int(qlen[0]) // TB)):
            poisoned = plane.copy()
            held = self.pages_needed(ctx, qlen, k, TB, psz, mp)
            poisoned[table[0, held[0]:]] = np.nan
            out = np.asarray(mla_attention.mla_paged_attention(
                q, jnp.asarray(poisoned)[None],
                jnp.asarray(table, jnp.int32), jnp.asarray(ctx, jnp.int32),
                jnp.asarray(qlen, jnp.int32), rank=R, interpret=True))
            live = slice(k * TB, min((k + 1) * TB, int(qlen[0])))
            np.testing.assert_allclose(out[0, live], want[0, live],
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("Tq", [1, 128])
    def test_stacked_bf16_pool_with_a_traced_layer(self, Tq):
        """The call of the model's layer scan: the whole [L, P, psz, W]
        bf16 plane, the layer a traced scalar, under jit."""
        rng = np.random.RandomState(3 + Tq)
        nH, W, R, psz, mp = self.nH, self.W, self.R, self.psz, self.max_pages
        L, B = 3, 5
        table = jnp.asarray(1 + rng.permutation(B * mp).reshape(B, mp),
                            jnp.int32)
        ctx = jnp.asarray([0, 130, 15, 190, 63], jnp.int32)
        qlen = jnp.asarray(rng.randint(0, Tq + 1, size=B), jnp.int32)
        q = jnp.asarray(rng.standard_normal((B, Tq, nH, W)) * 0.1,
                        jnp.bfloat16)
        pool = jnp.asarray(rng.standard_normal((L, 1 + B * mp, psz, W)),
                           jnp.bfloat16)
        call = jax.jit(lambda lay: mla_attention.mla_paged_attention(
            q, pool, table, ctx, qlen, layer=lay, rank=R, interpret=True))
        for lay in (0, 2):
            p, rows = mla_dense(q, pool[lay], table, ctx)
            want = np.einsum("bthw,bwr->bthr", p, rows[..., :R])
            # bf16 probabilities and output: 2^-8 of values of order 1
            assert_mla_rows(call(jnp.int32(lay)), want, qlen,
                            max(1, min(Tq, mla_attention.QUERY_ROWS // nH)),
                            tol=2e-2)

    @staticmethod
    def kernel_equations(Tq, psz, max_pages, B=4):
        """Equations in the kernel's body, inner jaxprs (the rolled
        loops, ``pl.when`` branches) included: what every start traces
        and lowers, cache hit or not."""
        S = jax.ShapeDtypeStruct
        jaxpr = jax.make_jaxpr(lambda *a: mla_attention.mla_paged_attention(
            *a, layer=jnp.int32(1), rank=512))(
                S((B, Tq, 128, 640), jnp.bfloat16),
                S((3, 1 + B * max_pages, psz, 640), jnp.bfloat16),
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

    @pytest.mark.parametrize("Tq", [1, 512])
    def test_traced_body_does_not_grow_with_the_table_or_the_block(self,
                                                                    Tq):
        """Set-up is traced and lowered at every start: the kernel's
        body is the same size whatever the table's width (16, 96, 128
        page slots) and whatever the block (4 to 32 pages), and small."""
        base = self.kernel_equations(Tq, 16, 96)
        assert base < 300      # 281: 4 copies a trip at 3 copy sites
        for psz, max_pages in ((16, 16), (16, 128), (8, 96), (64, 96)):
            assert self.kernel_equations(Tq, psz, max_pages) == base, \
                (psz, max_pages)


def test_grouped_matmul_skips_absent_and_unpicked_experts():
    rng = np.random.RandomState(1)
    N, k, E, H, F = 24, 4, 4, 128, 128
    local = rng.randint(-6, E + 6, (N * k,)).astype(np.int32)
    local[local == 2] = 1                       # expert 2: nobody picks it
    valid = (local >= 0) & (local < E) & (rng.rand(N * k) < 0.9)
    row, sizes_, tile_expert, n_tiles = grouped_matmul.sort_picks(
        jnp.asarray(local), jnp.asarray(valid), E)
    assert int(sizes_[2]) == 0 and 2 not in np.asarray(
        tile_expert)[: int(n_tiles[0])]
    assert int(sizes_.sum()) == valid.sum()
    rows = grouped_matmul.buffer_rows(N * k, E)
    x = rng.standard_normal((N * k, H)).astype(np.float32)
    xs = np.zeros((rows, H), np.float32)
    xs[np.asarray(row)[valid]] = x[valid]
    assert len(set(np.asarray(row)[valid])) == valid.sum()   # none dropped
    wg, wu = (rng.standard_normal((E, H, F)).astype(np.float32) * 0.1
              for _ in range(2))
    wd = rng.standard_normal((E, F, H)).astype(np.float32) * 0.1
    mid = grouped_matmul.grouped_expert_matmul(
        jnp.asarray(xs), (jnp.asarray(wg), jnp.asarray(wu)), tile_expert,
        n_tiles, swiglu=True, interpret=True)
    ys = grouped_matmul.grouped_expert_matmul(
        mid, jnp.asarray(wd), tile_expert, n_tiles, interpret=True)
    for i in np.flatnonzero(valid):
        e = local[i]
        g, u = x[i] @ wg[e], x[i] @ wu[e]
        want = (g / (1 + np.exp(-g)) * u) @ wd[e]
        np.testing.assert_allclose(np.asarray(ys)[int(row[i])], want,
                                   rtol=2e-3, atol=2e-3)


# (c) ----------------------------------------------------------------------

def test_all_shares_add_up_to_the_uncut_layer():
    """The routed parts of all four shares, with attention and the shared
    expert counted once, are the uncut reference layer."""
    set_mesh(None)
    whole = lm.LatentMoEConfig.tiny()
    params = jiggle(lm.init_params(whole, jax.random.PRNGKey(2)))
    m = sizes(whole)
    lp = {k: v[1] for k, v in params["moe"].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 12, whole.hidden_size))
    positions = jnp.arange(12)[None]
    valid = jnp.ones((12,), bool)
    x1 = jax.jit(lambda x, lp: lm._causal_latent_attention(
        whole, x, lp, positions))(x, lp)
    h = llama._rms_norm(x1, lp["n3"], whole.rms_eps)[0]
    picks, w = lm.route(whole, h, lp["router"])
    total = lm._shared_expert(h, lp)
    hit = 0
    for c in range(4):
        share = lm.LatentMoEConfig.tiny(held_experts=(4 * c, 4))
        sp = lm.share_params(params, whole, share)
        assert sp["moe"]["we_gate"].shape[1] == 4
        part, cnt = jax.jit(lambda h, p, w, lp: lm._routed_experts(
            share, h, p, w, valid, lp))(
                h, picks, w, {k: v[1] for k, v in sp["moe"].items()})
        total = total + part
        hit += int(cnt[1])
    assert hit == 12 * whole.num_experts_per_tok    # every pick once
    got = x1[0] + llama._rms_norm(total, lp["n4"], whole.rms_eps)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda x, lp: ref.layer(
            x, lp, m, (0, whole.n_routed_experts)))(x[0], lp)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    # and one share alone is NOT the layer
    assert not np.allclose(
        x1[0] + llama._rms_norm(lm._shared_expert(h, lp) + part, lp["n4"],
                                whole.rms_eps), want, atol=1e-2)


# (d) ----------------------------------------------------------------------

def test_router_picks_the_references_experts(tiny):
    cfg, params = tiny
    h = jax.random.normal(jax.random.PRNGKey(7), (40, cfg.hidden_size))
    rw = params["moe"]["router"][0]
    picks, w = lm.route(cfg, h, rw)
    with jax.default_matmul_precision("highest"):
        rp, rwt, _ = ref.route(h, rw, sizes(cfg))
    assert np.array_equal(np.sort(picks, -1), np.sort(rp, -1))
    np.testing.assert_allclose(np.sort(w, -1), np.sort(rwt, -1), rtol=1e-5)
    np.testing.assert_allclose(w.sum(-1), cfg.routed_scaling_factor,
                               rtol=1e-5)


def _softmax_route(h, router_w, m):
    scores = jax.nn.softmax(h @ router_w, -1)
    top, picks = jax.lax.top_k(scores, m["num_experts_per_tok"])
    return picks, m["routed_scaling_factor"] * top / top.sum(
        -1, keepdims=True), scores


_route = ref.route       # the real one, whatever a test plants in its place


def _unnormalised_route(h, router_w, m):
    picks, _, scores = _route(h, router_w, m)
    return picks, m["routed_scaling_factor"] * jnp.take_along_axis(
        scores, picks, 1), scores


def _unscaled_route(h, router_w, m):
    picks, w, scores = _route(h, router_w, m)
    return picks, w / m["routed_scaling_factor"], scores


@pytest.mark.parametrize("fault", ["softmax_for_sigmoid", "not_renormalised",
                                   "scaling_left_out", "post_norm_dropped",
                                   "ffn_out_norm_dropped"])
def test_a_planted_fault_in_the_reference_shows(tiny, monkeypatch, fault):
    """The comparison of (a) has teeth: the program no longer agrees with
    a reference that routes by softmax, skips the renormalisation or the
    scaling, or drops one of the four norms."""
    cfg, params = tiny
    got, full, m = paged_run(cfg, params, PROMPTS[:1], 2)
    if fault.endswith("norm_dropped"):
        gone = "n2" if fault.startswith("post") else "n4"
        real = ref._rms
        monkeypatch.setattr(
            ref, "layer", lambda x, w, m, held: _layer_without(
                real, gone, x, w, m, held))
    else:
        monkeypatch.setattr(ref, "route", {
            "softmax_for_sigmoid": _softmax_route,
            "not_renormalised": _unnormalised_route,
            "scaling_left_out": _unscaled_route}[fault])
    want = ref_logits(params, full[0], m, cfg.experts)
    assert not np.allclose(got[0][0], want[len(PROMPTS[0]) - 1], rtol=2e-3,
                           atol=2e-3)


def _layer_without(rms, gone, x, w, m, held):
    eps = m["rms_norm_eps"]
    norm = lambda v, name: v if name == gone else rms(v, w[name], eps)
    x = x + norm(ref.attention(norm(x, "n1"), w, m), "n2")
    return x + norm(ref.ffn(norm(x, "n3"), w, m, held), "n4")


# (e) ----------------------------------------------------------------------

def test_mtp_module_matches_reference(tiny):
    cfg, params = tiny
    mtp = jiggle(lm.init_mtp_params(cfg, jax.random.PRNGKey(9)), seed=4)
    hidden = jax.random.normal(jax.random.PRNGKey(10),
                               (1, 10, cfg.hidden_size))
    nxt = jnp.asarray(np.random.RandomState(2).randint(0, 256, (1, 10)),
                      jnp.int32)
    got = jax.jit(lambda *a: lm.mtp_logits(*a, cfg))(params, mtp, hidden,
                                                     nxt)
    want = jax.jit(lambda *a: ref.mtp_logits(*a, sizes(cfg), cfg.experts))(
        params, mtp, hidden[0], nxt[0])
    np.testing.assert_allclose(got[0], want, rtol=2e-3, atol=2e-3)
    # the segment program never calls it: its parameters are a tree apart
    assert "mtp" not in params


# (f), (h) -----------------------------------------------------------------

def engine(cfg, params, **kw):
    return ServingEngine(cfg, params, slots=4, max_len=64, paged=True,
                         page_size=PSZ, prompt_buckets=(16,), **kw)


def requests():
    rng = np.random.RandomState(0)
    return [Arrival(0.0, rng.randint(0, 256, (n,)).astype(np.int32), g)
            for n, g in [(5, 6), (16, 4), (9, 8), (3, 5), (12, 7), (7, 3)]]


def serve(cfg, params):
    eng = engine(cfg, params)
    sched = OnlineScheduler(eng, max_queue=8, seg_steps=8)
    report = sched.serve(requests())
    return report, sched.results(), eng


def test_engine_serves_the_references_greedy_tokens(tiny):
    cfg, params = tiny
    assert family_of(cfg) is lm and family_of(llama.LlamaConfig.tiny()) \
        is llama
    report, results, eng = serve(cfg, params)
    m = sizes(cfg)
    rid0 = min(results)
    for rid, toks in results.items():
        a = requests()[rid - rid0]
        assert len(toks) == a.max_new_tokens
        seq = np.concatenate([a.prompt, toks[:-1]]).astype(np.int32)
        lg = ref_logits(params, seq, m, cfg.experts)[len(a.prompt) - 1:]
        for t, row in zip(toks, lg):
            top2 = np.sort(row)[-2:]
            assert t == int(row.argmax()) or top2[1] - top2[0] < 1e-3
    # the counters rode the event log: 4 picks x 2 layers for every token
    # position computed (prompt rows + decode ticks)
    fed = sum(len(a.prompt) + a.max_new_tokens - 1 for a in requests())
    k = cfg.num_experts_per_tok * cfg.num_expert_layers
    assert report.moe["picks"] == k * fed
    assert 0 < report.moe["picks_held"] < report.moe["picks"]
    assert report.moe["experts_hit"] <= report.moe["steps"] \
        * cfg.num_expert_layers * SHARE[1]
    assert 1 <= report.moe["max_load"] <= 16


class _Collector:
    def __init__(self):
        self.seen = []

    def _host_event(self, name, start_ns, end_ns, kind):
        self.seen.append(name)


def test_counters_and_tokens_identical_with_a_trace_live(tiny, tmp_path):
    cfg, params = tiny
    rep1, toks1, _ = serve(cfg, params)
    c = _Collector()
    _hooks.COLLECTORS.append(c)
    jax.profiler.start_trace(str(tmp_path))
    try:
        rep2, toks2, _ = serve(cfg, params)
    finally:
        jax.profiler.stop_trace()
        _hooks.COLLECTORS.remove(c)
    assert toks1 == toks2 and rep1.moe == rep2.moe
    assert "serving.segment.telemetry" in c.seen


def test_scopes_are_in_the_program(tiny):
    cfg, params = tiny
    pool = lm.init_paged_pool(cfg, 4, PSZ)
    text = jax.jit(lambda p, t, pool, pt, pos: lm.forward_with_pages(
        p, t, cfg, pool, pt, pos)).lower(
            params, jnp.zeros((2, 1), jnp.int32), pool,
            jnp.ones((2, 3), jnp.int32), jnp.zeros((2,), jnp.int32)
    ).as_text(debug_info=True)
    for scope in ("latent_qkv", "kv_write", "attention", "post", "router",
                  "experts", "shared_expert", "head"):
        assert f"/{scope}/" in text or f"{scope}/" in text, scope


def test_page_bytes_come_from_the_model(tiny):
    from paddle_tpu.analysis.memory import page_bytes_for

    cfg, _ = tiny
    assert page_bytes_for(cfg, PSZ) == cfg.num_layers * PSZ * cfg.cache_row \
        * 4
    lc = llama.LlamaConfig.tiny()
    assert page_bytes_for(lc, 16) == 2 * lc.num_layers * 16 \
        * lc.num_kv_heads * lc.head_dim * 4
    eng = engine(cfg, jax.tree_util.tree_map(lambda a: a, tiny[1]))
    assert eng.pool_bytes == {"c": (cfg.num_layers * (4 * 8 + 1) * PSZ
                                    * cfg.cache_row * 4)}


# (g) ----------------------------------------------------------------------

@pytest.mark.parametrize("family,kw", [
    ("chunked prefill", dict(chunked_prefill=True)),
    ("speculative", dict(speculative=2)),
    ("speculative", dict(sampling={"temperature": 0.7})),
    ("quality digest", dict(quality_digest=True)),
    ("quantized pool", dict(quant="int8")),
    ("sequence-parallel prefill", dict(seq_parallel=2, long_buckets=(32,))),
    ("mesh", dict(mesh=object())),
])
def test_unsupported_engine_families_refuse_by_name(tiny, family, kw):
    cfg, params = tiny
    kw = dict(dict(paged=True, page_size=PSZ), **kw)
    with pytest.raises(ValueError, match=f"not served by the '{family}'"):
        ServingEngine(cfg, params, slots=2, max_len=64,
                      prompt_buckets=(16,), **kw)


def test_prefix_cache_tiers_and_disagg_refuse_by_name(tiny):
    from paddle_tpu.inference.disagg import DisaggRouter
    from paddle_tpu.inference.kv_tiers import HostTier
    from paddle_tpu.inference.prefix_cache import PagedPrefixCache

    cfg, params = tiny
    eng = engine(cfg, params)
    with pytest.raises(ValueError, match="'prefix cache'"):
        PagedPrefixCache(eng.pager)
    with pytest.raises(ValueError, match="'host tier'"):
        HostTier(eng.pager)
    with pytest.raises(ValueError, match="'disaggregated serving'"):
        DisaggRouter([eng], [engine(cfg, params)])
