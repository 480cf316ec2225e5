"""The window family's page program over a chunk of several pages of one
prompt (``PagedFamily.chunk_pages``), and the tick that hands a session
such chunks, at a tiny size on the CPU in float32: one m-page call against
m one-page calls, a last chunk of fewer real pages, the tick's page budget
and its turn, the served tokens against an engine of one page a program,
a family of one page a program scheduled as it always was, and the prefix
cache keeping one page a program. The programs of the families that take
one page hash as they did."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oncilla_tpu.models import swa_moe as sm
from test_swa_moe import P, _family_case, seeded, serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K = sm.PAGED_FAMILY.chunk_pages


@pytest.fixture(scope="module", params=["tiny", "tiny_softmax"])
def shape(request):
    """Laguna's shape (gates, a dense layer, a shared expert, sigmoid) and
    Mellum2's (none of them, softmax)."""
    cfg = getattr(sm.SwaMoeConfig, request.param)()
    params = (seeded(cfg) if request.param == "tiny"
              else sm.init_params(jax.random.key(4), cfg))
    return cfg, params


def zeros_tails(cfg):
    return tuple(jnp.zeros(s, jnp.float32)
                 for s in sm.PAGED_FAMILY.leaf_shapes(cfg, P))


def context(cfg, pages, start):
    """The page program's context at page ``start`` as the engine hands it:
    every full-kind page, the window-kind pages not yet dropped (a page
    that starts at s goes once s + P <= pos - window), and its meta."""
    pos = start * P
    dropped = max((pos - cfg.sliding_window) // P, 0)
    full = [page[:2] for page in pages[:start]]
    window = [page[2:] for page in pages[dropped:start]]
    meta = jnp.asarray([pos, 0, dropped * P], jnp.int32)
    return meta, sm._context((full, window), cfg, P)


def one_page_calls(cfg, params, tokens, first, count, pages):
    """Pages ``first`` .. ``first + count - 1`` of ``tokens``, a one-page
    program each, over the pages before them (``pages``, extended in
    place): (each page's last logits, its tails)."""
    out = []
    for c in range(first, first + count):
        meta, ctx = context(cfg, pages, c)
        chunk = jnp.asarray([tokens[c * P:(c + 1) * P]], jnp.int32)
        logits, tails, _ = sm.swa_decode_page_jit(
            params, chunk, meta, ctx, zeros_tails(cfg), cfg)
        pages.append(tails)
        out.append((np.asarray(logits[0, -1]), tails))
    return out


def chunk_call(cfg, params, tokens, first, real, pages):
    """ONE call of the K-page program at page ``first``, ``real`` of its
    pages real, the rest of its rows the given tokens or padding."""
    meta, ctx = context(cfg, pages, first)
    rows = list(tokens[first * P:(first + real) * P])
    rows += list(tokens[(first + real) * P:(first + K) * P])
    rows += [0] * (K * P - len(rows))
    return sm.swa_decode_page_jit(
        params, jnp.asarray([rows], jnp.int32), meta, ctx, zeros_tails(cfg),
        cfg, np.int32(real))


def distinct_experts(cfg, params, tokens, lo, hi):
    """Distinct (layer, expert) pairs the rows [lo, hi) chose, by the
    unpaged program's routing."""
    _, routing = sm.forward(params, jnp.asarray([tokens], jnp.int32), cfg,
                            return_routing=True)
    routing = np.asarray(routing)[:, 0, lo:hi]
    return sum(len(np.unique(layer)) for layer in routing)


def same_pages(got, want):
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)


@pytest.mark.parametrize("first", [0, 5])
def test_one_chunk_is_m_one_page_calls(shape, first):
    """m = K pages from an empty context, and from page 5 (position 20, past
    the window of 10: the window kind holds its last 3 pages), so that the
    chunk straddles the window's edge again and again: each page's last
    logits and its K and V are the one-page calls'."""
    cfg, params = shape
    tokens = np.random.default_rng(first).integers(
        1, cfg.vocab, (first + K) * P).tolist()
    pages = []
    one_page_calls(cfg, params, tokens, 0, first, pages)
    if first:
        meta, ctx = context(cfg, pages, first)
        assert np.asarray(meta).tolist() == [20, 0, 8]
        assert ctx[2].shape[3] == cfg.window_pages(P) * P
    logits, made, touched = chunk_call(cfg, params, tokens, first, K,
                                       list(pages))
    want = one_page_calls(cfg, params, tokens, first, K, pages)
    assert logits.shape == (1, K, cfg.vocab) and len(made) == K
    assert np.abs(np.asarray(logits)).max() > 0.1
    for j, (last, tails) in enumerate(want):
        np.testing.assert_allclose(np.asarray(logits[0, j]), last,
                                   atol=2e-5)
        assert [a.shape for a in made[j]] == [a.shape for a in tails]
        same_pages(made[j], tails)
    assert int(touched) == distinct_experts(
        cfg, params, tokens, first * P, (first + K) * P)


def test_a_last_chunk_of_fewer_pages(shape):
    """r = 3 real pages of K: the real pages are the one-page calls', and
    the padded rows choose no expert: the count is the r pages' alone,
    whatever tokens the padding holds, and what an r-page call counts."""
    cfg, params = shape
    r = 3
    tokens = np.random.default_rng(9).integers(
        1, cfg.vocab, (2 + K) * P).tolist()
    pages = []
    one_page_calls(cfg, params, tokens, 0, 2, pages)
    logits, made, touched = chunk_call(cfg, params, tokens, 2, r,
                                       list(pages))
    padded = list(tokens[:(2 + r) * P])
    _, _, touched_zeros = chunk_call(cfg, params, padded, 2, r, list(pages))
    want = one_page_calls(cfg, params, tokens, 2, r, pages)
    for j, (last, tails) in enumerate(want):
        np.testing.assert_allclose(np.asarray(logits[0, j]), last,
                                   atol=2e-5)
        same_pages(made[j], tails)
    count = distinct_experts(cfg, params, tokens, 2 * P, (2 + r) * P)
    assert int(touched) == int(touched_zeros) == count
    assert count < distinct_experts(cfg, params, tokens, 2 * P, (2 + K) * P)


# The programs of the accepted cells as scripts/program_keys.py hashes them
# at its tiny shapes, before the page program took several pages: the
# one-page program (the benchmark's warmer compiles it), every program of
# the four other families and the engine's own.
ONE_PAGE_KEYS = {
    "dense.step": "80092cc9681d549f",
    "dense.page": "7e188089f8415534",
    "dense.row": "c04fd1817f995bdf",
    "latent.step": "767f391f4ccbf369",
    "latent.page": "958ee52a449a9903",
    "latent.row": "2142ee0cb5ec554a",
    "kda.step": "4950c64ee2f05d84",
    "kda.page": "800cf172aac2962e",
    "swa.step": "bd10da1af4a5d2fa",
    "swa.page": "03e621c209ec3216",
    "seat.write": "ccc29026dde1376d",
    "seat.move": "b3a0e9ad2d9933cd",
    "seat.read": "999487af78d8b346",
    "pool.write": "b69eccd3d8601c84",
    "pool.gather": "6c68aba681045e96",
    "mellum.step": "080336f0ef653ea3",
    "mellum.page": "8101c6a7b9e00e40",
    "conv.step": "87a9dad2ea09b3de",
    "conv.page": "d0b4769332e71fe2",
}


def test_the_one_page_programs_hash_as_they_did():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "program_keys.py"),
         ROOT], capture_output=True, text=True, check=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    keys = json.loads(out.stdout)
    assert {k: keys[k] for k in ONE_PAGE_KEYS} == ONE_PAGE_KEYS
    assert set(keys) - set(ONE_PAGE_KEYS) == {"swa.chunk", "mellum.chunk"}
    assert keys["swa.chunk"] != keys["swa.page"]


# -- the tick -------------------------------------------------------------------


def watch_chunks(log, ticks=None):
    """Log every chunk as (tick, tenant, pos, pages taken) and, a tick, the
    sessions that could prefill (tenant, whole pages left) when the chunks
    began."""
    def watch(eng):
        chunk, turn = eng._prefill_chunk, eng._prefill_turn

        def logged(sess, most=1):
            pos = sess.pos
            pages = chunk(sess, most)
            log.append((eng._ticks, sess.req.tenant, pos, pages))
            return pages

        def turned(ready):
            if ticks is not None:
                ticks[eng._ticks] = [
                    (s.req.tenant, (len(s.prompt) - s.prompt_consumed) // P)
                    for s in eng.active if eng._bulk_prefill(s)]
            return turn(ready)

        eng._prefill_chunk, eng._prefill_turn = logged, turned
    return watch


@pytest.mark.parametrize("lens", [
    (61, 45, 70),                                   # n < K
    (41, 57, 38, 66, 49, 53, 44, 62, 59, 35, 47, 70),  # n > K
])
def test_a_tick_takes_its_budget_and_serves_every_session_in_turn(lens):
    """A tick's real pages reach max(n, K) and pass it by less than K
    (while the pages are there), and every session that can prefill is
    served within ceil(n K / max(n, K)) ticks of becoming ready."""
    cfg = sm.SwaMoeConfig.tiny()
    params = seeded(cfg)
    rng = np.random.default_rng(len(lens))
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in lens]
    log, ticks = [], {}
    _, meta = serve(cfg, params, prompts, [3] * len(lens),
                    max_active=len(lens), max_batch=len(lens),
                    watch=watch_chunks(log, ticks))
    assert meta["prefill"]["pages"] == sum(n // P for n in lens)
    assert meta["batch"]["prefill_chunks"] == len(log)
    full = 0
    for t, ready in ticks.items():
        n, left = len(ready), sum(p for _, p in ready)
        budget = max(n, K)
        taken = sum(p for tick, _, _, p in log if tick == t)
        assert min(budget, left) <= taken < budget + K
        full += taken >= budget
        assert all(p <= K for tick, _, _, p in log if tick == t)
        # each ready session waits its turn: served within the bound
        wait = -(-n * K // budget)
        for tenant, _ in ready:
            served = [tick for tick, who, _, _ in log
                      if who == tenant and tick >= t]
            assert served and served[0] - t < wait
    assert full > len(ticks) // 2


def test_served_tokens_are_an_engine_of_one_page_a_programs():
    """The same requests, K pages a program and one: the same tokens, and
    logits equal to float32 summation order."""
    cfg = sm.SwaMoeConfig.tiny_softmax()
    params = sm.init_params(jax.random.key(6), cfg)
    lens, new = (61, 23, 45, 38), (7, 12, 5, 9)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in lens]

    def one_page(eng):
        eng.family = dataclasses.replace(eng.family, chunk_pages=1)

    runs = [serve(cfg, params, prompts, new, max_active=4, max_batch=4,
                  watch=watch)
            for watch in (None, one_page)]
    (chunked, meta), (paged, meta_one) = runs
    assert meta["prefill"]["pages"] == meta_one["prefill"]["pages"] == sum(
        n // P for n in lens)
    assert meta_one["batch"]["prefill_chunks"] == sum(n // P for n in lens)
    assert meta["batch"]["prefill_chunks"] == sum(
        -(-(n // P) // K) for n in lens)
    for tenant, res in chunked.items():
        assert res.out_tokens == paged[tenant].out_tokens
        np.testing.assert_allclose(np.stack(res.out_logits),
                                   np.stack(paged[tenant].out_logits),
                                   atol=1e-4)


def test_a_family_of_one_page_a_program_keeps_its_schedule():
    """The dense family states no chunk_pages: every tick, every session
    that can prefill when the chunks begin takes one page, in admission
    order, as before chunks of several pages."""
    from oncilla_tpu.serving.engine import family_of

    cfg, params = _family_case("dense")
    assert family_of(cfg).chunk_pages == 1
    lens = (29, 13, 42, 7, 33, 21)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab, n).tolist() for n in lens]
    log, ticks = [], {}
    _, meta = serve(cfg, params, prompts, (6, 9, 4, 8, 5, 7), max_active=4,
                    max_batch=3, watch=watch_chunks(log, ticks))
    want = [(t, tenant, None, 1) for t, ready in sorted(ticks.items())
            for tenant, _ in ready]
    assert [(t, who, None, p) for t, who, _, p in log] == want
    # and each page where the session stood
    for i, n in enumerate(lens):
        assert [pos for _, who, pos, _ in log if who == f"t{i}"] == [
            P * k for k in range(n // P)]
    assert meta["prefill"]["pages"] == meta["batch"]["prefill_chunks"] == sum(
        n // P for n in lens)


def test_with_the_prefix_cache_a_program_takes_one_page():
    """A family that states several pages a program, served with the
    prefix cache on, takes one page a program: the re-probe before each is
    what lets identical prompts share pages."""
    from oncilla_tpu.serving.engine import Request
    from test_serving_batched import build_engine

    model = _family_case("dense")
    shared = list(range(1, 40))
    prompts = [shared + [50 + i] * (9 + 5 * i) for i in range(3)]
    log = []
    ctx, store, eng = build_engine(model, share=True, hot=64, max_active=3,
                                   max_batch=3)
    try:
        eng.family = dataclasses.replace(eng.family, chunk_pages=K)
        watch_chunks(log)(eng)
        for i, p in enumerate(prompts):
            eng.submit(Request(tenant=f"t{i}", tokens=p, max_new_tokens=4))
        results = eng.run()
        meta = eng.metrics_meta()
    finally:
        eng.close()
        store.close()
        ctx.tini()
    assert log and all(p == 1 for *_, p in log)
    assert meta["prefill"]["pages"] == meta["batch"]["prefill_chunks"]
    assert sum(r.prefix_tokens_reused for r in results) > 0
