"""Flagship model tests: forward correctness, ring-vs-dense equivalence,
sharded train step on the (dp, tp, sp) mesh, KV-cache decode consistency."""

import jax
import jax.numpy as jnp
import numpy as np

from oncilla_tpu.models import llama, train


CFG = llama.LlamaConfig.tiny()


def test_forward_shapes(rng):
    params = llama.init_params(jax.random.key(0), CFG)
    tokens = train.sample_batch(rng, CFG, 2, 32)
    logits = llama.forward(params, tokens, CFG)
    assert logits.shape == (2, 32, CFG.vocab)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_ring_forward_matches_dense(rng):
    mesh = train.make_mesh()  # 2x2x2 over the 8 virtual devices
    assert dict(mesh.shape) == {"dp": 2, "tp": 2, "sp": 2}
    params = llama.init_params(jax.random.key(0), CFG)
    tokens = train.sample_batch(rng, CFG, 2, 64)
    dense = llama.forward(params, tokens, CFG)
    sparams = train.shard_params(params, mesh, CFG)
    ring = llama.forward(sparams, tokens, CFG, mesh=mesh, seq_axis=train.SP)
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(dense), atol=2e-4, rtol=2e-4
    )


def test_sharded_train_step_loss_decreases(rng):
    mesh = train.make_mesh()
    params, opt_state, tx = train.make_train_state(jax.random.key(1), CFG, mesh)
    step = train.make_train_step(CFG, mesh, tx)
    tokens = train.sample_batch(rng, CFG, 4, 64)
    losses = []
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, tokens)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    # Overfitting one batch must reduce loss materially.
    assert losses[-1] < losses[0] - 0.1, losses


def test_decode_matches_forward(rng):
    """Greedy decode with a KV cache reproduces teacher-forced logits."""
    params = llama.init_params(jax.random.key(2), CFG)
    tokens = train.sample_batch(rng, CFG, 1, 16)
    full = llama.forward(params, tokens, CFG)  # (1, 16, V)

    cfg = CFG
    kv = llama.make_kv_cache(cfg, 1, dtype="float32")
    step = jax.jit(
        lambda p, t, pos, kv: llama.decode_step(p, t, pos, kv, cfg)
    )
    for i in range(16):
        logits, kv = step(params, tokens[:, i], jnp.int32(i), kv)
        np.testing.assert_allclose(
            np.asarray(logits[0]), np.asarray(full[0, i]), atol=2e-3, rtol=2e-3
        )


def test_mesh_factoring():
    m = train.make_mesh(8)
    assert m.devices.size == 8
    m4 = train.make_mesh(4)
    assert m4.devices.size == 4 and dict(m4.shape)["sp"] == 2
    m2 = train.make_mesh(2)
    assert dict(m2.shape) == {"dp": 1, "tp": 2, "sp": 1}
    m1 = train.make_mesh(1)
    assert m1.devices.size == 1


def test_ring_matches_dense_bf16(rng):
    # Regression: ring attention must accumulate in fp32 so bf16 models get
    # the same logits from the ring and dense paths.
    import jax.numpy as jnp
    from dataclasses import replace

    cfg = replace(CFG, dtype="bfloat16")
    mesh = train.make_mesh()
    params = llama.init_params(jax.random.key(5), cfg)
    tokens = train.sample_batch(rng, cfg, 2, 64)
    dense = llama.forward(params, tokens, cfg)
    ring = llama.forward(
        train.shard_params(params, mesh, cfg), tokens, cfg,
        mesh=mesh, seq_axis=train.SP,
    )
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(dense), atol=5e-2, rtol=5e-2
    )


def test_init_params_host_matches_pytree():
    # init_params_host must stay structurally identical to init_params
    # (same leaves, shapes, dtypes) — it exists to skip on-device random
    # kernel compiles, not to define a different model.
    import jax

    a = llama.init_params(jax.random.key(0), CFG)
    b = llama.init_params_host(0, CFG)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    sa = jax.tree.map(lambda x: (x.shape, str(x.dtype)), a)
    sb = jax.tree.map(lambda x: (x.shape, str(x.dtype)), b)
    assert sa == sb


def test_decode_loop_matches_forward(rng):
    """The single-dispatch scan decode (llama.decode_loop) reproduces the
    teacher-forced logits — same contract as the per-step decode."""
    params = llama.init_params(jax.random.key(4), CFG)
    tokens = train.sample_batch(rng, CFG, 2, 16)
    full = llama.forward(params, tokens, CFG)  # (2, 16, V)

    kv = llama.make_kv_cache(CFG, 2, dtype="float32")
    loop = jax.jit(
        lambda p, t, kv: llama.decode_loop(p, t, kv, CFG)
    )
    logits, kv_out = loop(params, tokens, kv)
    assert logits.shape == full.shape
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full), atol=2e-3, rtol=2e-3
    )
    # The final cache holds every position's K/V (non-zero through pos 15).
    assert float(jnp.abs(kv_out[0][:, :, :, 15, :]).max()) > 0.0


def test_generate_greedy_matches_stepwise(rng):
    """generate() (prefill scan + sample scan, one program) reproduces the
    hand-rolled greedy loop over decode_step."""
    params = llama.init_params(jax.random.key(5), CFG)
    prompt = train.sample_batch(rng, CFG, 2, 8)
    steps = 6

    # Hand-rolled greedy reference.
    kv = llama.make_kv_cache(CFG, 2, dtype="float32")
    logits = None
    for i in range(8):
        logits, kv = llama.decode_step(params, prompt[:, i], jnp.int32(i), kv, CFG)
    want = []
    tok = jnp.argmax(logits, axis=-1).astype(prompt.dtype)
    for j in range(steps):
        want.append(tok)
        if j < steps - 1:
            logits, kv = llama.decode_step(
                params, tok, jnp.int32(8 + j), kv, CFG
            )
            tok = jnp.argmax(logits, axis=-1).astype(prompt.dtype)
    want = jnp.stack(want, axis=1)  # (B, steps)

    kv2 = llama.make_kv_cache(CFG, 2, dtype="float32")
    got, kv_out = jax.jit(
        llama.generate,
        static_argnames=("cfg", "steps", "temperature"),
        donate_argnums=(2,),
    )(params, prompt, kv2, CFG, steps)
    assert got.shape == (2, steps)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # The returned cache covers every consumed token: prompt + the first
    # steps-1 samples (the final sample is output-only).
    assert float(jnp.abs(kv_out[0][:, :, :, 8 + steps - 2, :]).max()) > 0.0
    assert float(jnp.abs(kv_out[0][:, :, :, 8 + steps - 1, :]).max()) == 0.0


def test_generate_temperature_sampling_valid(rng):
    """Temperature sampling returns in-vocab ids and is deterministic for
    a fixed key."""
    params = llama.init_params(jax.random.key(6), CFG)
    prompt = train.sample_batch(rng, CFG, 1, 4)
    kv = llama.make_kv_cache(CFG, 1, dtype="float32")
    a, _ = llama.generate(
        params, prompt, kv, CFG, 5, key=jax.random.key(7), temperature=1.0
    )
    kv = llama.make_kv_cache(CFG, 1, dtype="float32")
    b, _ = llama.generate(
        params, prompt, kv, CFG, 5, key=jax.random.key(7), temperature=1.0
    )
    assert a.shape == (1, 5)
    assert np.all((np.asarray(a) >= 0) & (np.asarray(a) < CFG.vocab))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_remat_train_step_matches_plain(rng):
    """remat=True (jax.checkpoint per block) must not change the math —
    same loss trajectory as the plain step from the same init."""
    mesh = train.make_mesh(8)
    tokens = jax.device_put(
        train.sample_batch(rng, CFG, 4, 32),
        jax.sharding.NamedSharding(mesh, train.data_spec()),
    )
    losses = {}
    for remat in (False, True):
        params, opt_state, tx = train.make_train_state(
            jax.random.key(9), CFG, mesh, lr=1e-2
        )
        step = train.make_train_step(CFG, mesh, tx, remat=remat)
        ls = []
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, tokens)
            ls.append(float(loss))
        losses[remat] = ls
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)


def test_sliding_window_masks_history(rng):
    """Windowed forward: logits differ from full-causal once S > window,
    and match a hand-built band mask exactly."""
    from dataclasses import replace

    cfg_w = replace(CFG, window=4)
    params = llama.init_params(jax.random.key(11), CFG)
    tokens = train.sample_batch(rng, CFG, 1, 12)
    full = llama.forward(params, tokens, CFG)
    windowed = llama.forward(params, tokens, cfg_w)
    # Positions < window see identical context; later ones must differ.
    np.testing.assert_allclose(
        np.asarray(windowed[0, :4]), np.asarray(full[0, :4]), atol=1e-5
    )
    assert not np.allclose(np.asarray(windowed[0, -1]), np.asarray(full[0, -1]))
    # The mask itself: band of width `window` under the diagonal.
    m = np.asarray(llama.causal_mask(6, 6, window=3))
    want = np.array([[j <= i and j > i - 3 for j in range(6)] for i in range(6)])
    np.testing.assert_array_equal(m, want)


def test_sliding_window_decode_matches_forward(rng):
    """Windowed cached decode (and the scan decode) reproduce the windowed
    teacher-forced logits."""
    from dataclasses import replace

    cfg_w = replace(CFG, window=4)
    params = llama.init_params(jax.random.key(12), CFG)
    tokens = train.sample_batch(rng, CFG, 1, 10)
    full = llama.forward(params, tokens, cfg_w)

    kv = llama.make_kv_cache(cfg_w, 1, dtype="float32")
    for i in range(10):
        logits, kv = llama.decode_step(
            params, tokens[:, i], jnp.int32(i), kv, cfg_w
        )
        np.testing.assert_allclose(
            np.asarray(logits[0]), np.asarray(full[0, i]),
            atol=2e-3, rtol=2e-3, err_msg=f"pos {i}",
        )

    kv = llama.make_kv_cache(cfg_w, 1, dtype="float32")
    loop_logits, _ = llama.decode_loop(params, tokens, kv, cfg_w)
    np.testing.assert_allclose(
        np.asarray(loop_logits), np.asarray(full), atol=2e-3, rtol=2e-3
    )


def test_sliding_window_paged_decode(rng):
    """Windowed decode with KV paged through OCM matches windowed cached
    decode."""
    from dataclasses import replace

    import oncilla_tpu as ocm_pkg
    from oncilla_tpu.models.kv_paging import BucketedPagedDecoder

    cfg_w = replace(CFG, window=4, max_seq=32)
    params = llama.init_params(jax.random.key(13), CFG)
    tokens = train.sample_batch(rng, cfg_w, 1, 12)

    kv = llama.make_kv_cache(cfg_w, 1, dtype="float32")
    want = []
    for i in range(12):
        logits, kv = llama.decode_step(
            params, tokens[:, i], jnp.int32(i), kv, cfg_w
        )
        want.append(np.asarray(logits[0]))

    ctx = ocm_pkg.ocm_init(ocm_pkg.OcmConfig(
        host_arena_bytes=16 << 20, device_arena_bytes=1 << 20,
    ))
    try:
        dec = BucketedPagedDecoder(
            params, cfg_w, ctx, batch=1, page_tokens=4,
            kind=ocm_pkg.OcmKind.LOCAL_HOST, dtype="float32",
        )
        for i in range(12):
            logits = dec.step(tokens[:, i])
            np.testing.assert_allclose(
                np.asarray(logits[0]), want[i], atol=2e-3, rtol=2e-3,
                err_msg=f"pos {i}",
            )
        dec.close()
    finally:
        ctx.tini()


def test_sliding_window_ring_matches_dense(rng):
    """Windowed ring attention over the sp-sharded axis equals the
    windowed dense forward — the band mask composes with the ring's
    global-position bookkeeping."""
    from dataclasses import replace

    cfg = replace(CFG, window=10)  # spans chunk boundaries on sp=2
    mesh = train.make_mesh()  # dp2 x tp2 x sp2
    params = llama.init_params(jax.random.key(15), CFG)
    tokens = train.sample_batch(rng, cfg, 2, 64)
    dense = llama.forward(params, tokens, cfg)
    ring = llama.forward(
        train.shard_params(params, mesh, cfg), tokens, cfg,
        mesh=mesh, seq_axis=train.SP,
    )
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(dense), atol=2e-4, rtol=2e-4
    )
    # Sanity: the window really bit (differs from full causal).
    full = llama.forward(params, tokens, CFG)
    assert not np.allclose(np.asarray(dense), np.asarray(full))


def test_sliding_window_paged_eviction(rng):
    """Long windowed paged decode: out-of-window pages are freed from OCM
    (O(window) working set) and logits still match plain windowed decode."""
    from dataclasses import replace

    import oncilla_tpu as ocm_pkg
    from oncilla_tpu.models.kv_paging import BucketedPagedDecoder

    cfg_w = replace(CFG, window=6, max_seq=64)
    params = llama.init_params(jax.random.key(14), CFG)
    N, page = 40, 4
    tokens = train.sample_batch(rng, cfg_w, 1, N)

    kv = llama.make_kv_cache(cfg_w, 1, dtype="float32")
    want = []
    for i in range(N):
        logits, kv = llama.decode_step(
            params, tokens[:, i], jnp.int32(i), kv, cfg_w
        )
        want.append(np.asarray(logits[0]))

    ctx = ocm_pkg.ocm_init(ocm_pkg.OcmConfig(
        host_arena_bytes=16 << 20, device_arena_bytes=1 << 20,
    ))
    try:
        dec = BucketedPagedDecoder(
            params, cfg_w, ctx, batch=1, page_tokens=page,
            kind=ocm_pkg.OcmKind.LOCAL_HOST, dtype="float32",
        )
        for i in range(N):
            logits = dec.step(tokens[:, i])
            np.testing.assert_allclose(
                np.asarray(logits[0]), want[i], atol=2e-3, rtol=2e-3,
                err_msg=f"pos {i}",
            )
        # Retained pages cover at most window + one page of slack, not the
        # whole history (N/page = 10 pages were shipped).
        assert len(dec.cache.pages) <= (cfg_w.window // page) + 2, \
            len(dec.cache.pages)
        # The evicted pages' memory really went back to the arena.
        assert dec._ctx_start > 0
        dec.close()
    finally:
        ctx.tini()


def offloaded_optimizer_check():
    """offload_opt=True (Adam state in pinned host memory, in-jit
    transfers around the update) must not change the math, and the state
    must really live in pinned_host. Needs an accelerator; on the chip
    run it as ``python3 -c "import sys; sys.path.insert(0, 'tests');
    import test_model; test_model.offloaded_optimizer_check()"``."""
    mesh = train.make_mesh(1)
    tokens = jax.device_put(
        train.sample_batch(np.random.default_rng(1234), CFG, 4, 32),
        jax.sharding.NamedSharding(mesh, train.data_spec()),
    )
    losses = {}
    for off in (False, True):
        params, opt_state, tx = train.make_train_state(
            jax.random.key(9), CFG, mesh, lr=1e-2, offload_opt=off
        )
        step = train.make_train_step(
            CFG, mesh, tx, offload_opt=off,
            opt_state=opt_state if off else None,
        )
        ls = []
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, tokens)
            ls.append(float(loss))
        losses[off] = ls
        kinds = {x.sharding.memory_kind for x in jax.tree.leaves(opt_state)}
        assert kinds == ({"pinned_host"} if off else {"device"}), (off, kinds)
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)
    return losses


def test_offloaded_optimizer_matches_plain():
    import pytest

    if jax.default_backend() == "cpu":
        # Single-device CPU has no implementation of the placement custom
        # call (annotate_device_placement for Host) and multi-device CPU
        # trips a partitioner RET_CHECK, so offload is accelerator-only.
        pytest.skip("no pinned_host placement on the CPU backend")
    offloaded_optimizer_check()


def test_offload_flag_state_mismatch_raises():
    import optax
    import pytest

    mesh = train.make_mesh(8)
    tx = optax.adamw(3e-4, weight_decay=0.01)
    with pytest.raises(ValueError, match="opt_state_example"):
        train.make_train_step(CFG, mesh, tx, offload_opt=True)
    with pytest.raises(ValueError, match="offload_opt is False"):
        train.make_train_step(CFG, mesh, tx, opt_state=object())


def test_eval_step_and_perplexity(rng):
    """make_eval_step matches loss_fn; evaluate() aggregates correctly and
    training reduces eval perplexity on the training batch."""
    mesh = train.make_mesh(8)
    params, opt_state, tx = train.make_train_state(
        jax.random.key(30), CFG, mesh, lr=1e-2
    )
    step = train.make_train_step(CFG, mesh, tx)
    eval_step = train.make_eval_step(CFG, mesh)
    tokens = jax.device_put(
        train.sample_batch(rng, CFG, 4, 32),
        jax.sharding.NamedSharding(mesh, train.data_spec()),
    )

    before = train.evaluate(params, [tokens, tokens], eval_step)
    assert before["batches"] == 2
    np.testing.assert_allclose(
        before["loss"], float(llama.loss_fn(params, tokens, CFG)), rtol=1e-4
    )
    np.testing.assert_allclose(
        before["perplexity"], np.exp(before["loss"]), rtol=1e-6
    )

    for _ in range(5):
        params, opt_state, _ = step(params, opt_state, tokens)
    after = train.evaluate(params, [tokens], eval_step)
    assert after["perplexity"] < before["perplexity"]

    import pytest

    with pytest.raises(ValueError, match="empty"):
        train.evaluate(params, [], eval_step)


def test_evaluate_token_weighted(rng):
    """Uneven batch sizes: evaluate() weights by predicted-token count."""
    mesh = train.make_mesh(8)
    params = train.shard_params(llama.init_params(jax.random.key(31), CFG),
                                mesh, CFG)
    eval_step = train.make_eval_step(CFG, mesh)
    sh = jax.sharding.NamedSharding(mesh, train.data_spec())
    big = jax.device_put(train.sample_batch(rng, CFG, 8, 32), sh)
    small = jax.device_put(train.sample_batch(rng, CFG, 2, 32), sh)
    res = train.evaluate(params, [big, small], eval_step)
    l_big = float(llama.loss_fn(params, big, CFG))
    l_small = float(llama.loss_fn(params, small, CFG))
    want = (l_big * 8 * 31 + l_small * 2 * 31) / (8 * 31 + 2 * 31)
    np.testing.assert_allclose(res["loss"], want, rtol=1e-4)


def test_blocked_ce_matches_plain(rng):
    """blocked_cross_entropy (no (B,S,V) logits tensor) must equal the
    plain log_softmax CE, including when the sequence doesn't divide the
    block (padding + mask), and its gradients must match."""
    params = llama.init_params(jax.random.key(3), CFG)
    for seq in (32, 27):  # 27: pad path (block 8 -> pad 5)
        tokens = train.sample_batch(rng, CFG, 3, seq)
        plain = llama.loss_fn(params, tokens, CFG)
        blocked = llama.loss_fn(params, tokens, CFG, ce_block=8)
        np.testing.assert_allclose(
            float(blocked), float(plain), rtol=2e-6
        )
    g_plain = jax.grad(lambda p: llama.loss_fn(p, tokens, CFG))(params)
    g_blk = jax.grad(
        lambda p: llama.loss_fn(p, tokens, CFG, ce_block=8)
    )(params)
    for k in g_plain:
        np.testing.assert_allclose(
            np.asarray(g_blk[k], np.float32),
            np.asarray(g_plain[k], np.float32),
            rtol=5e-5, atol=1e-6, err_msg=k,
        )


def test_dots_remat_and_blocked_ce_train_step(rng):
    """remat="dots" + ce_block: same loss trajectory as the plain step
    (the variant mfu_train_best sweeps on the chip)."""
    mesh = train.make_mesh(8)
    tokens = jax.device_put(
        train.sample_batch(rng, CFG, 4, 32),
        jax.sharding.NamedSharding(mesh, train.data_spec()),
    )
    losses = {}
    for mode in ("plain", "dots"):
        params, opt_state, tx = train.make_train_state(
            jax.random.key(9), CFG, mesh, lr=1e-2
        )
        step = train.make_train_step(
            CFG, mesh, tx,
            remat="dots" if mode == "dots" else False,
            ce_block=8 if mode == "dots" else None,
        )
        ls = []
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, tokens)
            ls.append(float(loss))
        losses[mode] = ls
    np.testing.assert_allclose(losses["dots"], losses["plain"], rtol=1e-5)


def test_step_page_matches_per_token(rng):
    """The page program (one dispatch per page, its tokens through each
    layer together) produces the same logits as the per-token bucketed
    decoder, with and without a sliding window, and interleaves with
    per-token steps at page boundaries."""
    from dataclasses import replace

    import oncilla_tpu as ocm_pkg
    from oncilla_tpu.models.kv_paging import BucketedPagedDecoder

    for window in (None, 4):
        cfg_w = replace(CFG, window=window, max_seq=32)
        params = llama.init_params(jax.random.key(13), CFG)
        tokens = train.sample_batch(rng, cfg_w, 1, 12)
        ctx = ocm_pkg.ocm_init(ocm_pkg.OcmConfig(
            host_arena_bytes=16 << 20, device_arena_bytes=1 << 20,
        ))
        try:
            kw = dict(batch=1, page_tokens=4,
                      kind=ocm_pkg.OcmKind.LOCAL_HOST, dtype="float32")
            ref = BucketedPagedDecoder(params, cfg_w, ctx, **kw)
            want = [np.asarray(ref.step(tokens[:, i])[0]) for i in range(12)]
            ref.close()

            dec = BucketedPagedDecoder(params, cfg_w, ctx, **kw)
            got = []
            # Page 0 fused, page 1 per-token, page 2 fused: both APIs
            # compose across boundaries.
            lg = dec.step_page(tokens[:, 0:4])
            got += [np.asarray(lg[0, j]) for j in range(4)]
            for i in range(4, 8):
                got.append(np.asarray(dec.step(tokens[:, i])[0]))
            lg = dec.step_page(tokens[:, 8:12])
            got += [np.asarray(lg[0, j]) for j in range(4)]
            dec.close()
            for i in range(12):
                np.testing.assert_allclose(
                    got[i], want[i], atol=2e-3, rtol=2e-3,
                    err_msg=f"window={window} pos {i}",
                )
            with np.testing.assert_raises(Exception):
                dec2 = BucketedPagedDecoder(params, cfg_w, ctx, **kw)
                dec2.step(tokens[:, 0])
                dec2.step_page(tokens[:, 1:5])  # tail not empty
        finally:
            ctx.tini()


def test_generate_page_matches_unpaged_generate(rng):
    """Greedy paged page-generation equals llama.generate's continuation:
    teacher-forced prefill via step_page, then one sampled page — the
    paged serving loop against the in-HBM reference."""
    from dataclasses import replace

    import oncilla_tpu as ocm_pkg
    from oncilla_tpu.models.kv_paging import BucketedPagedDecoder

    cfg_g = replace(CFG, max_seq=32)
    params = llama.init_params(jax.random.key(21), CFG)
    P = 4
    prompt = train.sample_batch(rng, cfg_g, 1, P)

    kv = llama.make_kv_cache(cfg_g, 1, dtype="float32")
    want, _ = llama.generate(params, prompt, kv, cfg_g, steps=P + 1)

    ctx = ocm_pkg.ocm_init(ocm_pkg.OcmConfig(
        host_arena_bytes=16 << 20, device_arena_bytes=1 << 20,
    ))
    try:
        dec = BucketedPagedDecoder(
            params, cfg_g, ctx, batch=1, page_tokens=P,
            kind=ocm_pkg.OcmKind.LOCAL_HOST, dtype="float32",
        )
        logits = dec.step_page(prompt)
        first = jnp.argmax(logits[:, -1], axis=-1).astype(prompt.dtype)
        np.testing.assert_array_equal(np.asarray(first), np.asarray(want[:, 0]))
        out = dec.generate_page(first)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want[:, 1:]))
        dec.close()

        # Sampling flavor: valid token range, deterministic under a key.
        dec2 = BucketedPagedDecoder(
            params, cfg_g, ctx, batch=1, page_tokens=P,
            kind=ocm_pkg.OcmKind.LOCAL_HOST, dtype="float32",
        )
        dec2.step_page(prompt)
        k = jax.random.key(5)
        s1 = np.asarray(dec2.generate_page(first, key=k, temperature=0.8))
        assert s1.shape == (1, P) and (s1 >= 0).all() and (s1 < CFG.vocab).all()
        dec2.close()
    finally:
        ctx.tini()


def test_blocked_ce_with_ring_attention(rng):
    """ce_block composes with sequence parallelism: the sp-sharded train
    step with blocked CE reproduces the plain step's loss trajectory
    (GSPMD reshards the chunked vocab-head scan correctly)."""
    mesh = train.make_mesh(8)
    assert dict(mesh.shape)[train.SP] == 2
    tokens = jax.device_put(
        train.sample_batch(rng, CFG, 4, 32),
        jax.sharding.NamedSharding(mesh, train.data_spec()),
    )
    losses = {}
    for ce in (None, 8):
        params, opt_state, tx = train.make_train_state(
            jax.random.key(9), CFG, mesh, lr=1e-2
        )
        step = train.make_train_step(CFG, mesh, tx, use_ring=True,
                                     ce_block=ce)
        ls = []
        for _ in range(3):
            params, opt_state, loss = step(params, opt_state, tokens)
            ls.append(float(loss))
        losses[ce] = ls
    np.testing.assert_allclose(losses[8], losses[None], rtol=1e-5)
