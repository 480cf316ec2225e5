"""Sharded training steps for the model families.

Mesh axes: ``dp`` (batch data parallel), ``tp`` (tensor parallel over
heads/ffn), ``sp`` (sequence parallel — ring attention), ``ep`` (expert
parallel — MoE all-to-all), ``pp`` (pipeline parallel — GPipe over
ppermute). Parameters are sharded with NamedSharding and GSPMD inserts the
collectives over ICI (all-reduce for dp grads, all-gather/reduce-scatter
for tp, all-to-all for ep) — the "pick a mesh, annotate shardings, let XLA
insert collectives" recipe; pp alone is explicit
(:mod:`oncilla_tpu.parallel.pipeline`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from oncilla_tpu.models.llama import LlamaConfig, init_params, loss_fn

DP, TP, SP, EP, PP = "dp", "tp", "sp", "ep", "pp"


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    """Factor the devices into a (dp, tp, sp) mesh: sp gets the largest
    power-of-two factor ≤ 2, tp next, rest dp — small meshes stay usable."""
    devices = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    sp = 2 if n % 2 == 0 and n >= 4 else 1
    tp = 2 if (n // sp) % 2 == 0 and (n // sp) >= 2 else 1
    dp = n // (sp * tp)
    arr = np.asarray(devices).reshape(dp, tp, sp)
    return Mesh(arr, (DP, TP, SP))


def param_specs(cfg: LlamaConfig) -> dict:
    """PartitionSpecs: heads/ffn over tp, vocab over tp for the big tables."""
    return {
        "embed": P(TP, None),
        "wq": P(None, None, TP),
        "wk": P(None, None, TP),
        "wv": P(None, None, TP),
        "wo": P(None, TP, None),
        "w_gate": P(None, None, TP),
        "w_up": P(None, None, TP),
        "w_down": P(None, TP, None),
        "ln_attn": P(None, None),
        "ln_mlp": P(None, None),
        "ln_out": P(None),
        "lm_head": P(None, TP),
    }


def shard_params(params: dict, mesh: Mesh, cfg: LlamaConfig) -> dict:
    specs = param_specs(cfg)
    return {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in params.items()
    }


def data_spec() -> P:
    # Batch over dp; sequence over sp (ring attention consumes it).
    return P(DP, SP)


def _sharded_state(params_host: dict, specs: dict, mesh: Mesh, lr: float,
                   offload_opt: bool = False, mu_dtype=None):
    """Shared state factory: device_put each leaf under its spec + adamw.
    With ``offload_opt``, the optimizer state lives in the TPU-VM host's
    pinned memory (same partition specs, ``memory_kind="pinned_host"``) —
    the HBM footprint drops by ~2 weight copies and the step pays a
    host<->HBM round-trip for the moments (the ZeRO-offload trade, here a
    first-class placement like every other OCM memory kind).
    ``mu_dtype`` (e.g. ``jnp.bfloat16``) stores Adam's first moment in a
    reduced dtype (optax's native knob, cast up for the update math): µ
    traffic and footprint halve, the variance ν stays fp32 — the common
    memory-efficient-Adam deployment trade."""
    params = {
        k: jax.device_put(v, NamedSharding(mesh, specs[k]))
        for k, v in params_host.items()
    }
    tx = optax.adamw(lr, weight_decay=0.01, mu_dtype=mu_dtype)
    opt_state = tx.init(params)
    if offload_opt:
        opt_state = jax.tree.map(
            lambda x: jax.device_put(
                x,
                NamedSharding(
                    mesh, _spec_of(x), memory_kind="pinned_host"
                ),
            ),
            opt_state,
        )
    return params, opt_state, tx


def _spec_of(x) -> P:
    """The PartitionSpec a state leaf carries (replicated for leaves whose
    sharding type has no spec, e.g. scalars committed to one device)."""
    return getattr(x.sharding, "spec", P())


def _jit_step(loss_of, specs: dict, mesh: Mesh, data_pspec: P, tx,
              offload_opt: bool = False, opt_state_example=None,
              fold_steps: int = 0):
    """Shared step factory: jit value_and_grad + adamw update with the
    params' in/out shardings pinned. Output params MUST be pinned to the
    input specs, or the compiler may pick different output shardings and
    step N+1's input contract breaks (observed on the ep mesh). opt_state
    is deliberately unpinned on both sides: with no input constraint there
    is no contract to break, and the compiler keeps it consistent with the
    params it mirrors. With ``offload_opt``, ``opt_state_example`` (the
    host-resident state from the matching ``offload_opt=True`` state
    factory) supplies the per-leaf specs for the in-jit host<->device
    transfers around the optimizer update."""
    if not offload_opt and opt_state_example is not None:
        raise ValueError(
            "an opt_state example was passed but offload_opt is False — "
            "the offloaded (pinned_host) state needs offload_opt=True on "
            "the step too, or tx.update would run on host-resident moments"
        )
    if offload_opt:
        if opt_state_example is None:
            raise ValueError(
                "offload_opt needs opt_state_example (the state built by "
                "the matching make_*_train_state(offload_opt=True))"
            )
        opt_dev = jax.tree.map(
            lambda x: NamedSharding(mesh, _spec_of(x)), opt_state_example
        )
        opt_host = jax.tree.map(
            lambda x: NamedSharding(
                mesh, _spec_of(x), memory_kind="pinned_host"
            ),
            opt_state_example,
        )

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(lambda p: loss_of(p, tokens))(params)
        if offload_opt:
            opt_state = jax.tree.map(jax.device_put, opt_state, opt_dev)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if offload_opt:
            opt_state = jax.tree.map(jax.device_put, opt_state, opt_host)
        return params, opt_state, loss

    run = step
    if fold_steps:
        # ``fold_steps`` gradient steps on the same batch in ONE compiled
        # dispatch (lax.scan over the (params, opt_state) carry). Two uses:
        # tight inner training loops where per-step dispatch latency
        # matters, and an MFU window with no per-step host round-trips
        # in it (same rationale as ops/pallas_ici.pallas_read_rows_loop).
        def run(params, opt_state, tokens):
            def body(carry, _):
                p, o, loss = step(*carry, tokens)
                return (p, o), loss

            (params, opt_state), losses = jax.lax.scan(
                body, (params, opt_state), None, length=fold_steps
            )
            return params, opt_state, losses[-1]

    pshard = {k: NamedSharding(mesh, s) for k, s in specs.items()}
    dshard = NamedSharding(mesh, data_pspec)
    return jax.jit(
        run,
        in_shardings=(pshard, None, dshard),
        out_shardings=(pshard, None, None),
        donate_argnums=(0, 1),
    )


def make_train_state(key, cfg: LlamaConfig, mesh: Mesh, lr: float = 3e-4,
                     offload_opt: bool = False, mu_dtype=None):
    return _sharded_state(
        init_params(key, cfg), param_specs(cfg), mesh, lr,
        offload_opt=offload_opt, mu_dtype=mu_dtype,
    )


def make_train_state_host(seed: int, cfg: LlamaConfig, mesh: Mesh,
                          lr: float = 3e-4, offload_opt: bool = False,
                          mu_dtype=None):
    """Same state as :func:`make_train_state` but with numpy host-side
    param init (init values differ; optimizer identical) — the jax.random
    path compiles one kernel per weight shape. Benchmarks use this."""
    from oncilla_tpu.models.llama import init_params_host

    return _sharded_state(
        init_params_host(seed, cfg), param_specs(cfg), mesh, lr,
        offload_opt=offload_opt, mu_dtype=mu_dtype,
    )


def make_train_step(cfg: LlamaConfig, mesh: Mesh, tx, use_ring: bool = True,
                    remat=False, offload_opt: bool = False,
                    opt_state=None, ce_block: int | None = None,
                    fold_steps: int = 0):
    """The jitted full training step (forward + backward + adamw update),
    sharded over the (dp, tp, sp) mesh. ``remat`` checkpoints each block
    (recompute-in-backward) to fit longer sequences / bigger batches —
    ``True`` for the full checkpoint, ``"dots"`` for the dots-saveable
    policy (elementwise-only recompute); ``ce_block`` switches the loss to
    the blocked vocab-head CE (no (B, S, V) logits materialized);
    ``offload_opt`` keeps Adam state in TPU-VM host memory — pass the
    state built by ``make_train_state*(offload_opt=True)`` as
    ``opt_state`` so the step knows its leaf specs. ``fold_steps`` > 0
    returns a step that runs that many gradient steps on its batch in one
    compiled dispatch (see _jit_step).

    offload_opt platform note: TPU-only in the current jax/XLA build.
    The CPU backend cannot execute the memory-kind placement custom call
    at all — single-device CPU fails with "No registered implementation
    for ... annotate_device_placement for Host", and multi-device CPU
    trips a legacy SPMD-partitioner RET_CHECK ("Side-effect HLO must
    have sharding"). Verified working on the real chip (see
    tests/test_model.py's real-chip subprocess test)."""
    seq_axis = SP if use_ring and mesh.shape[SP] > 1 else None
    return _jit_step(
        lambda p, tokens: loss_fn(
            p, tokens, cfg, mesh=mesh, seq_axis=seq_axis, remat=remat,
            ce_block=ce_block,
        ),
        param_specs(cfg), mesh, data_spec(), tx,
        offload_opt=offload_opt, opt_state_example=opt_state,
        fold_steps=fold_steps,
    )


def sample_batch(rng: np.random.Generator, cfg: LlamaConfig, batch: int, seq: int):
    return jnp.asarray(
        rng.integers(0, cfg.vocab, size=(batch, seq), dtype=np.int32)
    )


def make_eval_step(cfg: LlamaConfig, mesh: Mesh, use_ring: bool = True):
    """Jitted evaluation step: mean next-token cross entropy for a (B, S)
    batch, sharded like the train step (no grads, params donated never)."""
    seq_axis = SP if use_ring and mesh.shape[SP] > 1 else None

    def step(params, tokens):
        return loss_fn(params, tokens, cfg, mesh=mesh, seq_axis=seq_axis)

    pshard = {k: NamedSharding(mesh, s) for k, s in param_specs(cfg).items()}
    return jax.jit(
        step,
        in_shardings=(pshard, NamedSharding(mesh, data_spec())),
    )


def evaluate(params, batches, eval_step) -> dict:
    """Token-weighted mean loss and perplexity over an iterable of token
    batches (e.g. from :func:`oncilla_tpu.utils.data.prefetch_to_mesh`).

    Per-batch losses are weighted by their predicted-token count, so a
    smaller remainder batch doesn't bias the corpus perplexity; the
    device scalars accumulate asynchronously and materialize once at the
    end (no per-batch host sync — dispatch keeps overlapping compute)."""
    losses, weights = [], []
    n = 0
    for tokens in batches:
        losses.append(eval_step(params, tokens))
        # loss_fn averages over B*(S-1) predicted tokens.
        weights.append(tokens.shape[0] * (tokens.shape[1] - 1))
        n += 1
    if n == 0:
        raise ValueError("evaluate() got an empty batch iterable")
    w = np.asarray(weights, np.float64)
    ls = np.asarray([float(x) for x in losses], np.float64)
    mean = float((ls * w).sum() / w.sum())
    return {"loss": mean, "perplexity": float(np.exp(mean)), "batches": n}


# -- expert parallelism (MoE family) ---------------------------------------


def make_moe_mesh(n_devices: int | None = None, devices=None,
                  n_experts: int | None = None) -> Mesh:
    """Factor devices into a (dp, ep, tp) mesh: ep first (the MoE axis),
    then tp, rest dp.

    Without ``n_experts`` the factory keeps ep ≤ 2 (a balanced default
    that leaves devices for dp and tp on small meshes). Pass the model's
    expert count to let ep grow to the largest power-of-two divisor of
    the device count that does not exceed it — e.g. 8 experts on 8
    devices gives an (1, 8, 1) mesh with one expert shard per device."""
    devices = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    ep_cap = 2 if n_experts is None else n_experts
    ep = 1
    while ep * 2 <= ep_cap and n % (ep * 2) == 0:
        ep *= 2
    tp = 2 if (n // ep) % 2 == 0 else 1
    dp = n // (ep * tp)
    arr = np.asarray(devices).reshape(dp, ep, tp)
    return Mesh(arr, (DP, EP, TP))


def moe_param_specs(cfg) -> dict:
    """PartitionSpecs for the MoE family: experts over ep, heads/ffn over
    tp, router replicated (it is small and every token needs it)."""
    specs = dict(param_specs(cfg))
    for k in ("w_gate", "w_up", "w_down"):
        del specs[k]
    specs["w_router"] = P(None, None, None)
    specs["w_gate_e"] = P(None, EP, None, TP)
    specs["w_up_e"] = P(None, EP, None, TP)
    specs["w_down_e"] = P(None, EP, TP, None)
    return specs


def make_moe_train_state(key, cfg, mesh: Mesh, lr: float = 3e-4,
                         offload_opt: bool = False):
    from oncilla_tpu.models.moe import init_moe_params

    return _sharded_state(
        init_moe_params(key, cfg), moe_param_specs(cfg), mesh, lr,
        offload_opt=offload_opt,
    )


def make_moe_train_step(cfg, mesh: Mesh, tx, remat=False,
                        offload_opt: bool = False, opt_state=None,
                        ce_block: int | None = None):
    """Jitted MoE training step over the (dp, ep, tp) mesh: GSPMD lowers
    the dispatch/combine einsums to all-to-alls over the ep axis. Supports
    the same ``remat``/``ce_block``/``offload_opt`` memory trades as the
    dense step."""
    from oncilla_tpu.models import moe

    return _jit_step(
        lambda p, tokens: moe.loss_fn(
            p, tokens, cfg, mesh=mesh, ep_axis=EP, remat=remat,
            ce_block=ce_block,
        ),
        moe_param_specs(cfg), mesh, P(DP, None), tx,
        offload_opt=offload_opt, opt_state_example=opt_state,
    )


# -- pipeline parallelism --------------------------------------------------


def make_pp_mesh(
    n_devices: int | None = None, devices=None, n_layers: int = 4
) -> Mesh:
    """Factor devices into a (dp, pp) mesh: pp = the largest power of two
    ≤ 4 dividing both the device count and the layer count; rest dp."""
    devices = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    pp = 1
    for cand in (4, 2):
        if n % cand == 0 and n_layers % cand == 0:
            pp = cand
            break
    arr = np.asarray(devices).reshape(n // pp, pp)
    return Mesh(arr, (DP, PP))


def pp_param_specs(cfg: LlamaConfig) -> dict:
    """Layer-stacked leaves sharded over pp on the stacked axis; embed/
    norm/head replicated (they run outside the pipeline)."""
    from oncilla_tpu.models.llama import LAYER_KEYS, param_spec

    return {
        k: (P(PP) if k in LAYER_KEYS else P())
        for k in param_spec(cfg)
    }


def make_pp_train_state(key, cfg: LlamaConfig, mesh: Mesh, lr: float = 3e-4,
                        offload_opt: bool = False):
    return _sharded_state(
        init_params(key, cfg), pp_param_specs(cfg), mesh, lr,
        offload_opt=offload_opt,
    )


def moe_pp_param_specs(cfg) -> dict:
    """MoE leaves for the (dp, pp) mesh: layer-stacked leaves (attention +
    router + expert weights) sharded over pp; embed/norm/head replicated."""
    from oncilla_tpu.models.moe import MOE_LAYER_KEYS, moe_param_spec

    return {
        k: (P(PP) if k in MOE_LAYER_KEYS else P())
        for k in moe_param_spec(cfg)
    }


def make_moe_pp_train_state(key, cfg, mesh: Mesh, lr: float = 3e-4,
                            offload_opt: bool = False):
    from oncilla_tpu.models.moe import init_moe_params

    return _sharded_state(
        init_moe_params(key, cfg), moe_pp_param_specs(cfg), mesh, lr,
        offload_opt=offload_opt,
    )


def make_pp_stage_fn(cfg, moe_aux: bool = False):
    """The per-stage GPipe body shared by both families: a lax.scan over
    this stage's layer stack. With ``moe_aux`` the FFN is the expert
    layer and the stage returns (activations, summed router aux)."""
    from oncilla_tpu.models.llama import block, make_attend

    def stage_fn(stage_params, x):
        S = x.shape[1]
        positions = jnp.arange(S)
        attend = make_attend(S, window=cfg.window)

        if moe_aux:
            from oncilla_tpu.models.moe import moe_ffn

            def body(carry, lp):
                xc, aux = carry
                box = {}

                def mlp(hn, lp=lp, box=box):
                    y, a = moe_ffn(hn, lp, cfg)
                    box["aux"] = a
                    return y

                out = block(cfg, xc, lp, positions, attend, mlp=mlp)
                return (out, aux + box["aux"]), None

            (out, aux), _ = jax.lax.scan(
                body, (x, jnp.float32(0.0)), stage_params
            )
            return out, aux

        def body(xc, lp):
            return block(cfg, xc, lp, positions, attend), None

        out, _ = jax.lax.scan(body, x, stage_params)
        return out

    return stage_fn


def _make_pp_loss(cfg, mesh: Mesh, microbatches: int, layer_keys,
                  moe_aux: bool = False, remat: bool = False,
                  ce_block: int | None = None):
    """Shared GPipe loss: embed -> pipelined layer stack -> head -> CE
    (+ the scale-matched router aux for the MoE family). ``remat``
    checkpoints each stage application (recompute-in-backward per
    microbatch tick) — the same FLOPs-for-memory trade as the other
    families, applied at stage granularity."""
    from oncilla_tpu.models.llama import final_logits
    from oncilla_tpu.parallel.pipeline import pipeline_apply

    stage_fn = make_pp_stage_fn(cfg, moe_aux=moe_aux)
    if remat:
        stage_fn = jax.checkpoint(stage_fn)

    def pp_loss(params, tokens):
        x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
        blocks = {k: params[k] for k in layer_keys}
        res = pipeline_apply(
            stage_fn, blocks, x,
            mesh=mesh, axis_name=PP, batch_axis=DP,
            microbatches=microbatches, with_aux=moe_aux,
        )
        x, aux = res if moe_aux else (res, None)
        if ce_block is not None:
            from oncilla_tpu.models.llama import blocked_cross_entropy

            ce = blocked_cross_entropy(
                x=x, params=params, targets=tokens[:, 1:], cfg=cfg,
                block=ce_block,
            )
        else:
            logits = final_logits(params, x, cfg)
            targets = tokens[:, 1:]
            logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
            ll = jnp.take_along_axis(
                logp, targets[..., None], axis=-1
            )[..., 0]
            ce = -jnp.mean(ll)
        if moe_aux:
            # aux sums one O(1) load-balance term per (layer, microbatch);
            # divide by microbatches so the regularizer scale matches the
            # non-pipelined moe.loss_fn (one term per layer). Scale, not
            # value: under dp the pipelined aux is a pmean of per-dp-shard
            # load-balance terms (each over its local tokens), while the
            # non-pipelined family computes the term over the global
            # batch — a mean of ratios vs a ratio of means. Same
            # magnitude and gradient direction, not bit-identical; fine
            # for a regularizer, but don't assert numeric equality of the
            # two families' losses under dp.
            ce = ce + cfg.router_aux_weight * aux / microbatches
        return ce

    return pp_loss


def make_pp_train_step(cfg: LlamaConfig, mesh: Mesh, tx, microbatches: int = 2,
                       remat: bool = False, offload_opt: bool = False,
                       opt_state=None, ce_block: int | None = None):
    """Jitted GPipe training step over the (dp, pp) mesh: the stacked layer
    axis is sharded over pp; activations move stage-to-stage via ppermute
    (:mod:`oncilla_tpu.parallel.pipeline`); embed/head run replicated.
    Supports the same ``remat``/``offload_opt`` memory trades as the other
    step families."""
    from oncilla_tpu.models.llama import LAYER_KEYS

    return _jit_step(
        _make_pp_loss(cfg, mesh, microbatches, LAYER_KEYS, remat=remat,
                      ce_block=ce_block),
        pp_param_specs(cfg), mesh, P(DP, None), tx,
        offload_opt=offload_opt, opt_state_example=opt_state,
    )


def make_moe_pp_train_step(cfg, mesh: Mesh, tx, microbatches: int = 2,
                           remat: bool = False, offload_opt: bool = False,
                           opt_state=None, ce_block: int | None = None):
    """GPipe training step for the MoE family over the (dp, pp) mesh: the
    expert layers ride the pipeline like dense blocks, and the router
    load-balancing aux loss crosses it through the executor's aux channel
    (each stage contributes its layers' aux per real microbatch)."""
    from oncilla_tpu.models.moe import MOE_LAYER_KEYS

    return _jit_step(
        _make_pp_loss(cfg, mesh, microbatches, MOE_LAYER_KEYS, moe_aux=True,
                      remat=remat, ce_block=ce_block),
        moe_pp_param_specs(cfg), mesh, P(DP, None), tx,
        offload_opt=offload_opt, opt_state_example=opt_state,
    )
