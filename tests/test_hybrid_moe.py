"""The window / full attention sparse-expert decoder
(``models/hybrid_moe.py``) against its plain reference
(``tests/reference_hybrid_moe.py``), at a small size on the CPU: (a)
admissions then ticks through the row pages and the fixed parts, past twice
the window and on reused slots, (b) the window itself and the planted
faults, (c) the share, (d) the cache manager's two kinds, (e) the engine end
to end with its counters, (f) the kernels in interpret mode, at the
published head counts too, (g) the refusals and the scopes, (h) an admission
in row blocks against the same admission in one trip.

Tolerances: the program and the reference are both float32 here and differ
by the order of their sums (a paged gather and an online softmax against
one masked softmax; experts sorted into tiles against a dense mask):
logits of magnitude ~1 agree to 2e-3 absolute and relative, as in
``test_latent_moe.py``; where both sides run the SAME formulation the
comparison is bit for bit.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_hybrid_moe as ref
from paddle_tpu.inference.paged_kv import PagedKVCache
from paddle_tpu.inference.scheduler import Arrival, OnlineScheduler
from paddle_tpu.inference import serving
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import family_of, hybrid_moe as hm, latent_moe, require
from paddle_tpu.ops.pallas import (grouped_matmul, paged_attention,
                                   window_attention)
from paddle_tpu.parallel import set_mesh
from paddle_tpu.profiler import _hooks

PSZ = 8
SHARE = (4, 4)           # this chip holds experts 4..7 of 16
W = 8                    # the tiny window
MAX_PAGES = 8            # 64 positions a slot


def sizes(cfg, **over):
    """The config as the public config.json's keys (what the reference
    reads)."""
    m = {"num_attention_heads": cfg.num_heads,
         "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
         "layer_types": cfg.kinds, "sliding_window": cfg.sliding_window,
         "num_experts_per_tok": cfg.num_experts_per_tok,
         "routed_scaling_factor": cfg.routed_scaling_factor,
         "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta}
    m.update(over)
    return m


NORMS = {"n1", "n2", "nq", "nk", "ln_f"}


def jiggle(params, seed=3):
    """Norm scales away from 1, so that a dropped or misplaced norm
    shows."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    rng = np.random.RandomState(seed)
    out = []
    for path, a in leaves:
        if getattr(path[-1], "key", None) in NORMS:
            a = a * (1.0 + 0.3 * rng.standard_normal(a.shape)).astype(
                a.dtype)
        out.append(a)
    return jax.tree_util.tree_unflatten(tree, out)


_JITS = {}


def ref_logits(params, tokens, m, held, pad_to=64):
    """``ref.logits`` under one jit a (sizes, share): the sequence is
    padded to ``pad_to`` (causal: what follows a position changes nothing
    before it)."""
    key = (ref.attention, ref._rms, tuple(sorted(m.items())), held, pad_to)
    if key not in _JITS:
        _JITS[key] = jax.jit(lambda p, t: ref.logits(p, t, m, held))
    seq = np.zeros((pad_to,), np.int32)
    seq[:len(tokens)] = np.asarray(tokens)
    return np.asarray(_JITS[key](params, jnp.asarray(seq)))[:len(tokens)]


@pytest.fixture(scope="module")
def tiny():
    set_mesh(None)
    cfg = hm.HybridMoEConfig.tiny(held_experts=SHARE)
    params = jiggle(jax.jit(lambda k: hm.init_params(cfg, k))(
        jax.random.PRNGKey(1)))
    return cfg, params


@contextlib.contextmanager
def kernels_interpreted():
    with pytest.MonkeyPatch.context() as mp:
        for mod in (paged_attention, window_attention, grouped_matmul):
            mp.setattr(mod, "FORCE_INTERPRET", True)
        # a bucket of 32 rows crosses blocks
        mp.setattr(window_attention, "BLOCK", 8)
        yield


@pytest.fixture(params=[None, 8], ids=["one_trip", "block_8"])
def admit_block(request, monkeypatch):
    """The admission's row block as the module has it (wider than every
    bucket here: one trip) or forced to 8 rows (an engine then builds its
    programs anew: the process-wide store is keyed by the configuration)."""
    if request.param:
        monkeypatch.setattr(hm, "ADMIT_BLOCK", request.param)
        monkeypatch.setattr(serving, "_SHARED_PROGS", {})
    return request.param


def computed_rows(width, n):
    """Rows an admission of ``n`` prompt rows in a bucket of ``width``
    computes: its row blocks up to the prompt's end."""
    c = width if width % hm.ADMIT_BLOCK else hm.ADMIT_BLOCK
    return -(-n // c) * c


def tables(B):
    """Slot b: row pages 1 + b*MAX_PAGES .., fixed part b + 1."""
    pages = 1 + np.arange(B * MAX_PAGES, dtype=np.int32).reshape(B, -1)
    return np.concatenate(
        [pages, 1 + np.arange(B, dtype=np.int32)[:, None]], 1)


def fresh_pool(cfg, B):
    return hm.init_paged_pool(cfg, 1 + B * MAX_PAGES, PSZ,
                              fixed_parts=B + 1)


def paged_run(cfg, params, prompts, n_decode, widths=None, dead=(),
              pool=None):
    """Admit ``prompts`` one a slot (each padded to its ``widths`` entry,
    as the engine's admit branch pads to a bucket), then ``n_decode``
    ticks over all slots teacher-forced with the program's own tokens;
    slots in ``dead`` stop after the admission. Returns {slot: [logits at
    each fed position]}, the tokens fed, and the pool."""
    B = len(prompts)
    widths = widths or [32] * B

    # a jit of its own a call: the kernels' dispatch is read while tracing
    @jax.jit
    def forward(tokens, pool, table, pos, live=None, logit_pos=None):
        return hm.forward_with_pages(params, tokens, cfg, pool, table, pos,
                                     live=live, logit_pos=logit_pos,
                                     with_counters=True)

    pool = fresh_pool(cfg, B) if pool is None else pool
    table = tables(B)
    full = [np.concatenate([p, np.zeros(n_decode, np.int32)])
            for p in prompts]
    got = {b: [] for b in range(B)}
    for b, p in enumerate(prompts):
        row = np.zeros((1, widths[b]), np.int32)
        row[0, :len(p)] = p
        logits, pool, cnt = forward(
            jnp.asarray(row), pool, jnp.asarray(table[b:b + 1]),
            jnp.zeros((1,), jnp.int32), logit_pos=jnp.int32(len(p) - 1))
        assert list(np.asarray(cnt[4:])) == [
            0, 0, computed_rows(widths[b], len(p)), len(p)]
        got[b].append(np.asarray(logits[0]))
        full[b][len(p)] = int(np.argmax(logits[0]))
    pos = np.array([len(p) for p in prompts], np.int32)
    live = np.array([b not in dead for b in range(B)])
    n_win, n_full = (len(cfg.layers_of(k)) for k in (hm.WINDOW, hm.FULL))
    for _ in range(n_decode - 1):
        nxt = np.array([full[b][pos[b]] for b in range(B)], np.int32)
        logits, pool, cnt = forward(
            jnp.asarray(nxt[:, None]), pool, jnp.asarray(table),
            jnp.asarray(pos), live=jnp.asarray(live))
        assert int(cnt[0]) == cfg.num_experts_per_tok \
            * cfg.num_expert_layers * int(live.sum())
        assert list(np.asarray(cnt[4:])) == [
            n_full * int((pos + 1)[live].sum()),
            n_win * int(np.minimum(pos + 1, cfg.sliding_window)[live].sum()),
            0, 0]
        for b in range(B):
            if live[b]:
                got[b].append(np.asarray(logits[b]))
                full[b][pos[b] + 1] = int(np.argmax(logits[b]))
                pos[b] += 1
    return got, full, pool


RNG = np.random.RandomState(0)
# shorter than the window, across it, past twice it at admission already
PROMPTS = [RNG.randint(0, 256, (n,)).astype(np.int32) for n in (5, 13, 23)]


def check_against_reference(cfg, params, got, full, prompts, m=None):
    m = m or sizes(cfg)
    for b, rows in got.items():
        n0 = len(prompts[b])
        want = ref_logits(params, full[b], m, cfg.experts)
        for i, lg in enumerate(rows):
            np.testing.assert_allclose(lg, want[n0 - 1 + i], rtol=2e-3,
                                       atol=2e-3)


# (a) ----------------------------------------------------------------------

def test_admissions_then_ticks_through_both_caches_match_reference(
        tiny, admit_block):
    """Every sequence ends past 2 x the window (the ring wraps at least
    twice), admitted at three widths (one, two and three of four row
    blocks at a block of 8); slot 1 stops after its admission."""
    cfg, params = tiny
    got, full, _ = paged_run(cfg, params, PROMPTS, 22, widths=[8, 16, 32],
                             dead=(1,))
    assert len(got[1]) == 1 and len(got[0]) == 22
    assert len(PROMPTS[0]) + 21 > 2 * W
    check_against_reference(cfg, params, got, full, PROMPTS)


def test_a_reused_slot_starts_clean_by_the_mask(tiny):
    """Pages and fixed parts that hold another sequence's rows (no clear
    between) give bit for bit what a pool that never held anything gives:
    a row is seen only once its own sequence has written it."""
    cfg, params = tiny
    _, _, used = paged_run(cfg, params, PROMPTS[1:], 20)
    assert float(jnp.abs(used["wk"][:, 1]).max()) > 0
    again, full_a, _ = paged_run(cfg, params, PROMPTS[:2], 20, pool=used)
    fresh, full_f, _ = paged_run(cfg, params, PROMPTS[:2], 20)
    for b in range(2):
        np.testing.assert_array_equal(full_a[b], full_f[b])
        for a, f in zip(again[b], fresh[b]):
            np.testing.assert_array_equal(a, f)
    check_against_reference(cfg, params, again, full_a, PROMPTS[:2])


def test_dead_slots_and_padding_leave_both_caches_alone(tiny):
    """A dead slot's tick writes the trash page and the trash part only;
    an admission's padding rows never reach the fixed part, and land in
    the row pages past the prompt only."""
    cfg, params = tiny
    pool = jax.tree_util.tree_map(lambda a: a + 3.0, fresh_pool(cfg, 2))
    table = tables(2)
    forward = jax.jit(lambda t, pool, tab, pos, live=None, logit_pos=None:
                      hm.forward_with_pages(params, t, cfg, pool, tab, pos,
                                            live=live, logit_pos=logit_pos))
    _, pool2 = forward(
        jnp.asarray([[1], [2]], jnp.int32), pool, jnp.asarray(table),
        jnp.asarray([9, 11], jnp.int32), live=jnp.asarray([False, True]))
    for n in pool:
        a, b = np.asarray(pool[n]), np.asarray(pool2[n])
        mine = table[0, :-1] if n in "kv" else table[0, -1:]
        np.testing.assert_array_equal(a[:, mine], b[:, mine])
    assert not np.array_equal(np.asarray(pool["wk"])[:, 2],
                              np.asarray(pool2["wk"])[:, 2])
    # an admission of 5 rows in a bucket of 16: ring rows 5..7 untouched
    # ... by anything the prompt did not write
    row = np.zeros((1, 16), np.int32)
    row[0, :5] = PROMPTS[0]
    _, pool3 = forward(jnp.asarray(row), pool, jnp.asarray(table[:1]),
                       jnp.zeros((1,), jnp.int32), logit_pos=jnp.int32(4))
    wide = np.zeros((1, 32), np.int32)
    wide[0, :5] = PROMPTS[0]
    wide[0, 5:] = 77                      # other padding, other bucket
    _, pool4 = forward(jnp.asarray(wide), pool, jnp.asarray(table[:1]),
                       jnp.zeros((1,), jnp.int32), logit_pos=jnp.int32(4))
    # (two bucket widths are two matmul shapes: equal to rounding)
    for n in ("wk", "wv"):                # the part's first 5 rows: equal
        np.testing.assert_allclose(np.asarray(pool3[n])[:, 1, :5],
                                   np.asarray(pool4[n])[:, 1, :5],
                                   rtol=1e-4, atol=1e-5)
        # slot 1's part: untouched
        np.testing.assert_array_equal(np.asarray(pool3[n])[:, 2],
                                      np.asarray(pool[n])[:, 2])
    for n in ("k", "v"):                  # the prompt's rows: equal
        np.testing.assert_allclose(
            np.asarray(pool3[n])[:, table[0, 0], :5],
            np.asarray(pool4[n])[:, table[0, 0], :5], rtol=1e-4, atol=1e-5)


# (b) ----------------------------------------------------------------------

def _qkv_rows(seed, T=32, nH=8, Hkv=4, D=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (1, T, nH, D)),
            jax.random.normal(ks[1], (1, T, Hkv, D)),
            jax.random.normal(ks[2], (1, T, Hkv * D)))


@pytest.mark.parametrize("kernel", [False, True])
def test_a_token_beyond_the_window_moves_only_full_layers(tiny, kernel):
    cfg, _ = tiny
    q, k, v = _qkv_rows(0)
    s = 9                                  # the token that moves
    k2 = k.at[0, s].add(1.0)
    v2 = v.at[0, s].add(1.0)
    with kernels_interpreted() if kernel else contextlib.nullcontext():
        win = [np.asarray(hm._admit_attention(cfg, q, *kv, hm.WINDOW))
               for kv in ((k, v), (k2, v2))]
        full = [np.asarray(hm._admit_attention(cfg, q, *kv, hm.FULL))
                for kv in ((k, v), (k2, v2))]
    # queries at distance >= window from s (and those before s): the same
    # bits; queries within the window of s: moved
    np.testing.assert_array_equal(win[0][0, s + W:], win[1][0, s + W:])
    np.testing.assert_array_equal(win[0][0, :s], win[1][0, :s])
    assert np.abs(win[0][0, s:s + W] - win[1][0, s:s + W]).min(0).max() > 0
    assert np.abs(full[0][0, s + W:] - full[1][0, s + W:]).max() > 1e-3


def test_shifting_every_position_moves_neither_kind(tiny):
    """A full layer has no rotary: its q and k do not read the positions
    at all. A window layer's rotary is relative: shifted by 100 its
    attention's output is the same to rounding."""
    cfg, params = tiny
    lp = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 32, cfg.hidden_size))
    at = jnp.arange(32)[None]
    out = {}
    for kind in (hm.WINDOW, hm.FULL):
        for shift in (0, 100):
            q, k, v = hm._qkv(cfg, x, lp, at + shift, kind == hm.WINDOW)
            out[kind, shift] = np.asarray(
                hm._admit_attention(cfg, q, k, v, kind))
    np.testing.assert_array_equal(out[hm.FULL, 0], out[hm.FULL, 100])
    assert not np.array_equal(out[hm.WINDOW, 0], out[hm.WINDOW, 100])
    np.testing.assert_allclose(out[hm.WINDOW, 0], out[hm.WINDOW, 100],
                               rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("fault", [
    "rope_on_full", dict(sliding_window=W + 1), dict(sliding_window=W - 1),
    dict(layer_types=(hm.WINDOW,) * 4 + (hm.FULL,))])
def test_a_planted_fault_in_the_program_fails_parity(tiny, fault,
                                                     monkeypatch):
    """Rotary on the full layer (planted in the module's ``ROTARY_KINDS``:
    no configuration has it), the window off by one either way, the full
    layer in the wrong place: each is a program the parity test of (a)
    refuses."""
    cfg, params = tiny
    if fault == "rope_on_full":
        monkeypatch.setattr(hm, "ROTARY_KINDS", (hm.WINDOW, hm.FULL))
        fault = {}
    bad = hm.HybridMoEConfig.tiny(held_experts=SHARE, **fault)
    got, full, _ = paged_run(bad, params, PROMPTS[1:], 12)
    with pytest.raises(AssertionError):
        check_against_reference(cfg, params, got, full, PROMPTS[1:])


@pytest.mark.parametrize("fault", ["rope_on_full", "window_plus_one",
                                   "no_qk_norm"])
def test_a_planted_fault_in_the_reference_shows(tiny, fault, monkeypatch):
    cfg, params = tiny
    got, full, _ = paged_run(cfg, params, PROMPTS[1:], 12)
    m = sizes(cfg)
    if fault == "window_plus_one":
        m = sizes(cfg, sliding_window=W + 1)
    elif fault == "rope_on_full":
        # the full layers rotated, their mask kept (a window past the end)
        plain = ref.attention
        monkeypatch.setattr(
            ref, "attention", lambda h, w, m, kind: plain(
                h, w, dict(m, sliding_window=10 ** 6), "sliding_attention")
            if kind == "full_attention" else plain(h, w, m, kind))
    else:
        monkeypatch.setattr(ref, "_rms", lambda x, w, eps, _r=ref._rms:
                            x if w.shape[-1] == cfg.head_dim
                            else _r(x, w, eps))
    with pytest.raises(AssertionError):
        check_against_reference(cfg, params, got, full, PROMPTS[1:], m)


# (c) ----------------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Each of 8 shares (2 of 16 experts) computes its routed part; with
    the shared expert counted once they add up to the uncut reference's
    expert layer, and ``share_params`` of the uncut tree gives each
    share's weights."""
    whole = hm.HybridMoEConfig.tiny()
    params = hm.init_params(whole, jax.random.PRNGKey(2))
    lp = params["layers"][2]
    h = jax.random.normal(jax.random.PRNGKey(3), (24, whole.hidden_size))
    with jax.default_matmul_precision("highest"):
        want = ref.ffn(h, lp, sizes(whole), (0, 16))
        total = latent_moe._shared_expert(h, lp)
        for s in range(8):
            share = hm.HybridMoEConfig.tiny(held_experts=(2 * s, 2),
                                            vocab_slice=(32 * s, 32))
            mine = hm.share_params(params, whole, share)
            np.testing.assert_array_equal(
                mine["layers"][2]["we_up"], lp["we_up"][2 * s:2 * s + 2])
            np.testing.assert_array_equal(
                mine["embed"], params["embed"][32 * s:32 * s + 32])
            np.testing.assert_array_equal(
                mine["lm_head"], params["lm_head"][:, 32 * s:32 * s + 32])
            assert mine["layers"][2]["router"].shape == (128, 16)
            assert jax.tree_util.tree_map(jnp.shape, mine) == \
                jax.tree_util.tree_map(jnp.shape, jax.eval_shape(
                    lambda: hm.init_params(share)))

            @jax.jit
            def routed(h, lp):
                picks, w = latent_moe.route(share, h, lp["router"])
                return latent_moe._routed_experts(
                    share, h, picks, w, jnp.ones((24,), bool), lp)

            part, cnt = routed(h, mine["layers"][2])
            assert 0 < int(cnt[1]) < int(cnt[0])
            total = total + part
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)


# (d) ----------------------------------------------------------------------

def test_the_cache_manager_accounts_for_both_kinds(tiny):
    from paddle_tpu.analysis.memory import (fixed_part_bytes_for,
                                            page_bytes_for, pool_bytes_for)

    cfg, _ = tiny
    pager = PagedKVCache(cfg, slots=4, page_size=PSZ, num_pages=33,
                         max_pages=MAX_PAGES)
    row_bytes = 2 * cfg.kv_width * 4
    assert page_bytes_for(cfg, PSZ) == 1 * PSZ * row_bytes       # 1 full
    assert fixed_part_bytes_for(cfg) == 4 * W * row_bytes == \
        pager.fixed_part_bytes                                   # 4 window
    # what the pool allocates: 33 pages, 4 + 1 parts
    assert pool_bytes_for(cfg, 33, PSZ, fixed_parts=5) == \
        sum(int(a.nbytes) for a in pager.pool.values())
    assert pager.pool["wk"].shape == (4, 5, W, cfg.kv_width)
    assert pager.pool["k"].shape == (1, 33, PSZ, cfg.kv_width)
    assert pager.page_table.shape == (4, MAX_PAGES + 1) and \
        pager.table_width == MAX_PAGES + 1
    # a request's pages follow its length; its fixed part does not
    short, long_ = pager.reserved_bytes(9), pager.reserved_bytes(60)
    assert short == 2 * page_bytes_for(cfg, PSZ) + pager.fixed_part_bytes
    assert long_ == 8 * page_bytes_for(cfg, PSZ) + pager.fixed_part_bytes
    held = {}
    for slot, rows in enumerate((9, 60, 17)):
        pages, table_row = pager.reserve(rows)
        assert len(pages) == pager.pages_needed(rows)
        assert table_row.shape == (MAX_PAGES,)   # the part comes with the slot
        pager.install(slot, pages)
        held[slot] = pages
    st = pager.stats()
    assert st["pages_used"] == 2 + 8 + 3 and st["fixed_parts"] == 4 \
        and st["fixed_parts_held"] == 3 \
        and st["fixed_part_bytes"] == pager.fixed_part_bytes
    assert pager.leak_report() != []
    assert pager.free_slot(1) == 8
    assert pager.stats()["fixed_parts_held"] == 2
    pager.free_slot(0)
    pager.free_slot(2)
    assert pager.leak_report() == [] and pager.fixed_parts_held == 0
    # a fixed part is its slot's alone: no fork, no copy-on-write
    pager.install(0, pager.reserve(9)[0])
    with pytest.raises(RuntimeError, match="fixed part"):
        pager.fork_slot(0, 1)
    with pytest.raises(RuntimeError, match="fixed part"):
        pager.ensure_writable(0, 0)
    # the other families keep no fixed part and their table its width
    other = PagedKVCache(latent_moe.LatentMoEConfig.tiny(), slots=2,
                         page_size=PSZ, num_pages=9, max_pages=4)
    assert other.fixed_parts == 0 and other.table_width == 4 \
        and "fixed_parts" not in other.stats()


# (e) ----------------------------------------------------------------------

def engine(cfg, params, slots=4, **kw):
    return ServingEngine(cfg, params, slots=slots, max_len=64, paged=True,
                         page_size=PSZ, prompt_buckets=(32,), **kw)


def requests():
    rng = np.random.RandomState(0)
    return [Arrival(0.0, rng.randint(0, 256, (n,)).astype(np.int32), g)
            for n, g in [(5, 20), (23, 9), (9, 18), (3, 5), (30, 12),
                         (7, 3), (17, 25), (12, 7)]]


def serve(cfg, params, slots=4):
    from paddle_tpu.inference.program_space import WorkloadEnvelope

    eng = engine(cfg, params, slots)
    eng.aot_warmup(WorkloadEnvelope(max_prompt=32, max_new_tokens=25,
                                    seg_steps=(8,), resume=False))
    warm = list(eng._progs)
    sched = OnlineScheduler(eng, max_queue=8, seg_steps=8)
    report = sched.serve(requests())
    assert list(eng._progs) == warm, "a program was built after warm-up"
    return report, sched.results(), eng


def test_engine_serves_the_references_greedy_tokens(tiny, admit_block):
    cfg, params = tiny
    assert family_of(cfg) is hm
    report, results, eng = serve(cfg, params)
    m = sizes(cfg)
    rid0 = min(results)
    assert len(results) == len(requests())
    for rid, toks in results.items():
        a = requests()[rid - rid0]
        assert len(toks) == a.max_new_tokens
        seq = np.concatenate([a.prompt, toks[:-1]]).astype(np.int32)
        lg = ref_logits(params, seq, m, cfg.experts)[len(a.prompt) - 1:]
        for t, row in zip(toks, lg):
            top2 = np.sort(row)[-2:]
            assert t == int(row.argmax()) or top2[1] - top2[0] < 1e-3
    # both groups rode the event log
    fed = sum(len(a.prompt) + a.max_new_tokens - 1 for a in requests())
    k = cfg.num_experts_per_tok * cfg.num_expert_layers
    assert report.moe["picks"] == k * fed
    assert 0 < report.moe["picks_held"] < report.moe["picks"]
    # a tick at position p (p = prompt .. prompt + answer - 2) attends
    # p + 1 rows in the full layer, min(p + 1, window) in each window one
    ticks = [p + 1 for a in requests() for p in
             range(len(a.prompt), len(a.prompt) + a.max_new_tokens - 1)]
    assert report.counters["window"]["rows_full"] == sum(ticks)
    assert report.counters["window"]["rows_window"] == 4 * sum(min(t, W) for t in ticks)
    assert report.counters["window"]["admit_rows"] == \
        sum(computed_rows(32, len(a.prompt)) for a in requests())
    assert report.counters["window"]["admit_rows_used"] == \
        sum(len(a.prompt) for a in requests())
    assert report.counters["window"]["steps"] == report.moe["steps"]
    assert eng.pager.leak_report() == []
    assert eng.pager.stats()["fixed_parts_held"] == 0


def test_one_slot_engine_reuses_its_part_across_requests(tiny):
    """One slot: every request after the first lands on the part and the
    pages the one before it left, and is served the same tokens as by four
    slots."""
    cfg, params = tiny
    _, four, _ = serve(cfg, params)
    _, one, eng = serve(cfg, params, slots=1)
    assert eng.pager.fixed_parts == 2
    assert [one[r] for r in sorted(one)] == [four[r] for r in sorted(four)]


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
    assert toks1 == toks2 and rep1.counters == rep2.counters \
        and set(rep1.counters) == {"moe", "window"}
    assert "serving.segment.telemetry" in c.seen


# (f) ----------------------------------------------------------------------

def test_kernels_through_the_model_match_reference(tiny, admit_block):
    """The three kernels in interpret mode (the admission's over its own
    rows, the paged one over the row pages and over the fixed part as one
    page, the grouped expert matmul) give the reference's logits."""
    cfg, params = tiny
    before = (window_attention.selection_count(),
              paged_attention.selection_count())
    with kernels_interpreted():
        assert hm.paged_kernel_active(cfg, PSZ)
        got, full, _ = paged_run(cfg, params, PROMPTS[:2], 20)
    assert window_attention.selection_count() >= before[0] + 5
    assert paged_attention.selection_count() >= before[1] + 5
    check_against_reference(cfg, params, got, full, PROMPTS[:2])


@pytest.mark.parametrize("window", [W, None, 20, 128])
@pytest.mark.parametrize("heads", [(8, 4, 32), (64, 8, 128)])
def test_prefill_kernel_matches_the_masked_softmax(window, heads):
    """``windowed_prefill_attention`` interpreted against one masked
    softmax; (64, 8, 128) are the published head counts. Window 20 over
    blocks of 8 visits key blocks the window's lower edge crosses, blocks
    wholly inside the mask and the diagonal."""
    nH, Hkv, D = heads
    T, blk = (256, 128) if D == 128 else (48, 8)
    q, k, v = _qkv_rows(7, T, nH, Hkv, D)
    want = window_attention.xla_windowed_attention(
        q, k, v.reshape(1, T, Hkv, D), window)
    got = window_attention.windowed_prefill_attention(
        q, k.reshape(1, T, -1), v, window, block=blk, interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("window", [W, None])
def test_prefill_kernel_leaves_out_whole_blocks_of_padding(window):
    """Rows before ``n_valid`` are what they are without it, bit for bit
    (their keys lie before them); a block that starts at or past it is 0,
    and finite whatever the padding rows hold."""
    T, blk, n = 48, 8, 19
    q, k, v = _qkv_rows(9, T, 8, 4, 32)
    run = lambda q, n_valid: window_attention.windowed_prefill_attention(
        q, k.reshape(1, T, -1), v, window, n_valid, block=blk,
        interpret=True)
    whole = np.asarray(run(q, None))
    got = np.asarray(run(q.at[:, 24:].set(jnp.inf),
                         jnp.asarray([n], jnp.int32)))
    np.testing.assert_array_equal(got[:, :n], whole[:, :n])
    assert np.isfinite(got[:, :24]).all() and not got[:, 24:].any()


@pytest.mark.parametrize("heads", [(8, 4, 32, W), (64, 8, 128, 128)])
def test_tick_over_a_fixed_part_matches_the_gather(heads):
    """The paged kernel over fixed parts as pages of ``window`` rows
    (positions before, at and past the first wrap; a dead slot) against
    the gathered dense formulation."""
    nH, Hkv, D, win = heads
    cfg = hm.HybridMoEConfig.tiny(num_heads=nH, num_kv_heads=Hkv,
                                  head_dim=D, sliding_window=win)
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    B = 4
    q = jax.random.normal(ks[0], (B, 1, nH, D))
    kp = jax.random.normal(ks[1], (2, B + 1, win, Hkv * D))
    vp = jax.random.normal(ks[2], (2, B + 1, win, Hkv * D))
    part = jnp.asarray([[3], [1], [4], [2]], jnp.int32)
    pos = np.array([2, win - 1, win + 5, 3 * win + 1])
    ctx = jnp.asarray(np.minimum(pos, win - 1), jnp.int32)
    q_len = jnp.asarray([1, 1, 1, 0], jnp.int32)
    want = hm._tick_attention(cfg, q, kp, vp, 1, part, ctx, q_len,
                              hm.WINDOW)
    with kernels_interpreted():
        got = hm._tick_attention(cfg, q, kp, vp, 1, part, ctx, q_len,
                                 hm.WINDOW)
    np.testing.assert_allclose(got[:3], want[:3], rtol=1e-4, atol=1e-5)


# (h) ----------------------------------------------------------------------

BUCKET, BLOCK = 32, 8
PAD = 255                # the padding's token: no prompt below holds it


def admit(cfg, params, lengths, block, pad_from=None):
    """One admission of ``len(lengths)`` prompts in a bucket of 32 at a row
    block of ``block``; positions from ``pad_from`` on hold ``PAD``.
    Returns (logits, pool, counters)."""
    B = len(lengths)
    rng = np.random.RandomState(sum(lengths))
    tokens = np.zeros((B, BUCKET), np.int32)
    for b, n in enumerate(lengths):
        tokens[b, :n] = rng.randint(0, PAD, (n,))
    if pad_from is not None:
        tokens[:, pad_from:] = PAD
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(hm, "ADMIT_BLOCK", block)
        out = jax.jit(lambda t, pool: hm.forward_with_pages(
            params, t, cfg, pool, jnp.asarray(tables(B)),
            jnp.zeros((B,), jnp.int32),
            logit_pos=jnp.asarray(lengths, jnp.int32) - 1,
            with_counters=True))(jnp.asarray(tokens), fresh_pool(cfg, B))
    return jax.tree_util.tree_map(np.asarray, out)


def written(pool, lengths):
    """Every row the prompts' own rows wrote: a slot's row pages up to its
    length, and its whole fixed part (ring rows past a prompt shorter than
    the window hold the prompt's row 0: ``_admit``)."""
    rows = []
    for b, n in enumerate(lengths):
        pages = tables(len(lengths))[b]
        rows += [pool[m][:, pages[:-1]].reshape(
            pool[m].shape[0], -1, pool[m].shape[-1])[:, :n]
                 for m in ("k", "v")]
        rows += [pool[m][:, pages[-1]] for m in ("wk", "wv")]
    return rows


@pytest.mark.parametrize("lengths", [
    (1,), (BLOCK - 1,), (BLOCK,), (BLOCK + 1,), (2 * BLOCK,), (BUCKET,),
    (1, BLOCK + 1), (2 * BLOCK, BLOCK - 1), (BLOCK, BUCKET)],
    ids=lambda lengths: "x".join(map(str, lengths)))
def test_blocked_admission_is_the_one_shot_admission(tiny, lengths):
    """Row blocks of 8 under a trip count from the longest prompt against
    ONE trip over the bucket: the logits at each prompt's last row, every
    row both caches receive from a prompt's own rows, and the counters
    (two block widths are two matmul shapes: equal to rounding; the
    counters exactly, but for the rows computed)."""
    cfg, params = tiny
    logits, pool, cnt = admit(cfg, params, lengths, BLOCK)
    want_logits, want_pool, want_cnt = admit(cfg, params, lengths, BUCKET)
    np.testing.assert_allclose(logits, want_logits, rtol=1e-4, atol=1e-5)
    for got, want in zip(written(pool, lengths),
                         written(want_pool, lengths)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    names = hm.SEGMENT_COUNTERS
    got, want = dict(zip(names, cnt)), dict(zip(names, want_cnt))
    B, blocks = len(lengths), -(-max(lengths) // BLOCK)
    assert want.pop("admit_rows") == B * BUCKET
    assert got.pop("admit_rows") == B * blocks * BLOCK
    assert got == want and got["admit_rows_used"] == sum(lengths)


@pytest.mark.parametrize("lengths", [(3,), (BLOCK,), (BLOCK + 2, 5)],
                         ids=lambda lengths: "x".join(map(str, lengths)))
def test_rows_past_the_last_block_are_never_read(tiny, lengths):
    """NaN in the embedding of the tokens past the last row block: the
    logits and every row a prompt wrote are what they are without it, bit
    for bit, and the row pages past the last block hold zeros."""
    cfg, params = tiny
    end = -(-max(lengths) // BLOCK) * BLOCK
    logits, pool, cnt = admit(cfg, params, lengths, BLOCK, pad_from=end)
    poisoned = dict(params, embed=params["embed"].at[PAD].set(jnp.nan))
    got_logits, got_pool, got_cnt = admit(cfg, poisoned, lengths, BLOCK,
                                          pad_from=end)
    np.testing.assert_array_equal(got_logits, logits)
    np.testing.assert_array_equal(got_cnt, cnt)
    for got, want in zip(written(got_pool, lengths),
                         written(pool, lengths)):
        np.testing.assert_array_equal(got, want)
    mine = tables(len(lengths))[0, :-1]
    for m in ("k", "v"):
        rows = got_pool[m][:, mine].reshape(1, -1, cfg.kv_width)
        assert not rows[:, end:BUCKET].any() and rows[:, :end].any(-1).all()


def test_counters_keep_their_meaning_over_three_blocks(tiny):
    """A prompt of 20 rows at a block of 8: a held expert counts once a
    layer an admission however many blocks hit it, the picks are the
    prompt's, the rows computed are the three blocks'."""
    cfg, params = tiny
    _, _, cnt = admit(cfg, params, (20,), BLOCK)
    c = dict(zip(hm.SEGMENT_COUNTERS, cnt))
    layers = cfg.num_expert_layers
    # (32 picks a block over 16 experts, 4 held: a sum over the blocks
    # would pass the held experts)
    assert SHARE[1] * layers // 2 < c["experts_hit"] <= SHARE[1] * layers
    assert c["picks"] == cfg.num_experts_per_tok * 20 * layers
    assert 0 < c["max_load"] <= c["picks_held"] < c["picks"]
    assert (c["admit_rows"], c["admit_rows_used"]) == (3 * BLOCK, 20)
    assert (c["rows_full"], c["rows_window"]) == (0, 0)


# (g) ----------------------------------------------------------------------

@pytest.mark.parametrize("rows", [1, 32])
def test_scopes_are_in_the_program(tiny, rows):
    cfg, params = tiny
    pool = fresh_pool(cfg, 2)
    text = jax.jit(lambda p, t, pool, pt, pos: hm.forward_with_pages(
        p, t, cfg, pool, pt, pos)).lower(
            params, jnp.zeros((2, rows), jnp.int32), pool,
            jnp.asarray(tables(2)), jnp.zeros((2,), jnp.int32)
    ).as_text(debug_info=True)
    for scope in ("qkv", "kv_write", "attention_window", "attention_full",
                  "post", "router", "experts", "shared_expert", "dense_ffn",
                  "head"):
        assert f"/{scope}/" in text or f"{scope}/" in text, scope


def test_the_seam_names_four_families(tiny):
    from paddle_tpu.models import llama, power_retention

    cfg, _ = tiny
    assert family_of(cfg) is hm
    assert family_of(llama.LlamaConfig.tiny()) is llama
    assert family_of(latent_moe.LatentMoEConfig.tiny()) is latent_moe
    assert family_of(power_retention.PowerRetentionConfig.tiny()) \
        is power_retention
    assert hm.SEGMENT_COUNTERS == latent_moe.SEGMENT_COUNTERS + (
        "rows_full", "rows_window", "admit_rows", "admit_rows_used")
    require(cfg, "paged")
    with pytest.raises(ValueError, match="hybrid_moe is served by paged"):
        require(cfg, "prefix cache")


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
