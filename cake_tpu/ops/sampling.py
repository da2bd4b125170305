"""On-device token sampling.

The reference keeps sampling on-GPU so only 4 bytes/token cross the bus:
Gumbel-softmax sampling (ref: text_model.rs create_logits_processor) and a
scatter-based sign-aware repeat penalty (ref: text_model.rs
apply_repeat_penalty_gpu). Here everything — penalty, temperature, top-k,
top-p, gumbel argmax — runs inside the jitted decode step, and only the
sampled token id leaves the TPU.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """Static sampling parameters — one compiled decode step per config
    (matches ref Sampling enum: ArgMax / GumbelSoftmax / TopK / TopP /
    TopKThenTopP)."""
    temperature: float = 0.0
    top_k: int | None = None
    top_p: float | None = None
    repeat_penalty: float = 1.0
    repeat_last_n: int = 64


def apply_repeat_penalty(logits, recent_tokens, penalty: float):
    """Sign-aware repeat penalty on device.

    logits: [V] (unbatched — the scatter is along the vocab axis);
    recent_tokens: [N] int32 with -1 padding (dropped by the scatter).
    logit >= 0 -> logit/penalty, logit < 0 -> logit*penalty
    (ref: text_model.rs apply_repeat_penalty_gpu).
    """
    if logits.ndim != 1:
        raise ValueError("apply_repeat_penalty expects unbatched [V] logits")
    # -1 padding would wrap to the last vocab entry; remap to an out-of-bounds
    # positive index so mode="drop" discards it.
    idx = jnp.where(recent_tokens < 0, logits.shape[-1], recent_tokens)
    flagged = jnp.zeros(logits.shape, jnp.bool_).at[idx].set(True, mode="drop")
    penalized = jnp.where(logits >= 0, logits / penalty, logits * penalty)
    return jnp.where(flagged, penalized, logits)


def _gumbel(rng, shape):
    return jax.random.gumbel(rng, shape, dtype=jnp.float32)


def sample_argmax(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def sample_gumbel(logits, rng, temperature: float):
    """Gumbel-max sampling == categorical sampling, fully on device."""
    z = logits.astype(jnp.float32) / temperature + _gumbel(rng, logits.shape)
    return jnp.argmax(z, axis=-1).astype(jnp.int32)


def sample_top_k(logits, rng, k: int, temperature: float):
    vals, idx = jax.lax.top_k(logits.astype(jnp.float32), k)
    z = vals / temperature + _gumbel(rng, vals.shape)
    choice = jnp.argmax(z, axis=-1)
    return jnp.take_along_axis(idx, choice[..., None], axis=-1)[..., 0].astype(jnp.int32)


def _top_p_mask(sorted_probs, p: float):
    """Keep the smallest prefix of (descending) sorted probs whose mass >= p.
    A token is kept if the cumulative mass *before* it is < p."""
    cum = jnp.cumsum(sorted_probs, axis=-1)
    prev = cum - sorted_probs
    return prev < p


def _sort_with_order(keys):
    """One stable ascending sort along the last axis that hands back BOTH
    its outputs: the sorted keys and the ids they came from. jnp.argsort is
    this same two-operand lax.sort with the first output thrown away, and
    fetching the sorted values again with `keys[order]` is a gather as wide
    as the vocabulary: on the TPU it cost 9.4 ms a step at 8 slots against
    1.7 ms for the sort itself (PERF.md, PR 26)."""
    ids = jax.lax.broadcasted_iota(jnp.int32, keys.shape, keys.ndim - 1)
    return jax.lax.sort((keys, ids), dimension=-1, is_stable=True,
                        num_keys=1)


def sample_top_p(logits, rng, p: float, temperature: float):
    lf = logits.astype(jnp.float32) / temperature
    # one O(V log V) sort, ascending then reversed (ties -> HIGH id first)
    asc, asc_order = _sort_with_order(lf)
    sorted_logits, order = asc[..., ::-1], asc_order[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    keep = _top_p_mask(probs, p)
    masked = jnp.where(keep, sorted_logits, -jnp.inf)
    z = masked + _gumbel(rng, masked.shape)
    choice = jnp.argmax(z, axis=-1)
    return jnp.take_along_axis(order, choice[..., None], axis=-1)[..., 0].astype(jnp.int32)


def sample_top_k_top_p(logits, rng, k: int, p: float, temperature: float):
    vals, idx = jax.lax.top_k(logits.astype(jnp.float32), k)
    vals = vals / temperature
    probs = jax.nn.softmax(vals, axis=-1)
    keep = _top_p_mask(probs, p)
    masked = jnp.where(keep, vals, -jnp.inf)
    z = masked + _gumbel(rng, masked.shape)
    choice = jnp.argmax(z, axis=-1)
    return jnp.take_along_axis(idx, choice[..., None], axis=-1)[..., 0].astype(jnp.int32)


@jax.named_scope("cake.sample")
def sample(logits, rng, cfg: SamplingConfig, recent_tokens=None):
    """Dispatch on the static SamplingConfig (ref: create_logits_processor).

    logits: [V] ([B, V] allowed only when repeat_penalty is off — the
    penalty scatter is vocab-axis only). recent_tokens: [N] int32 (-1 padded).
    """
    if cfg.repeat_penalty != 1.0 and recent_tokens is not None:
        logits = apply_repeat_penalty(logits, recent_tokens, cfg.repeat_penalty)
    if cfg.temperature <= 0.0:
        return sample_argmax(logits)
    if cfg.top_k is None and cfg.top_p is None:
        return sample_gumbel(logits, rng, cfg.temperature)
    if cfg.top_k is not None and cfg.top_p is None:
        return sample_top_k(logits, rng, cfg.top_k, cfg.temperature)
    if cfg.top_k is None and cfg.top_p is not None:
        return sample_top_p(logits, rng, cfg.top_p, cfg.temperature)
    return sample_top_k_top_p(logits, rng, cfg.top_k, cfg.top_p, cfg.temperature)


@jax.named_scope("cake.sample")
def sample_traced(logits, rng, temperature, top_k, top_p, repeat_penalty,
                  recent_tokens):
    """Fully-traced sampling: every parameter is a runtime value, so ONE
    compiled program serves any mix of per-request configs — the batched
    continuous-batching decode step cannot afford a static SamplingConfig
    (each slot would multiply the executable count by the whole grid).

    logits: [V]; temperature/top_p/repeat_penalty: traced f32 scalars;
    top_k: traced int32 (>= V disables); recent_tokens: [N] int32, -1 padded.
    Disabled values: temperature <= 0 -> argmax, top_p >= 1.0 -> off,
    repeat_penalty == 1.0 -> identity (naturally, via the arithmetic).

    Equivalence to the static `sample` dispatch: temperature <= 0 matches
    sample_argmax after the same penalty (the sort of the negated logits is
    stable, so ties break to the lowest id exactly like jnp.argmax); the
    stochastic paths draw gumbel noise over the full sorted vocab instead
    of the top-k prefix, so they match in distribution, not per-key.

    The named scopes (obs.spans.SCOPE_CATALOG) are metadata for a device
    trace's reader: which of the four parts the step's time is in.
    """
    v = logits.shape[-1]
    with jax.named_scope("cake.sample.penalty"):
        lf = logits.astype(jnp.float32)
        # sign-aware repeat penalty with a traced strength (identity at 1.0)
        idx = jnp.where(recent_tokens < 0, v, recent_tokens)
        flagged = jnp.zeros((v,), jnp.bool_).at[idx].set(True, mode="drop")
        penalized = jnp.where(lf >= 0, lf / repeat_penalty,
                              lf * repeat_penalty)
        lf = jnp.where(flagged, penalized, lf)
    with jax.named_scope("cake.sample.sort"):
        # one descending sort serves argmax (rank 0), top-k (rank mask) and
        # top-p (cumulative-mass mask) — same O(V log V) the static top-p
        # pays. The sort's own first output is the sorted values (negated:
        # exact), so nothing gathers [V] by `order`
        scaled = lf / jnp.maximum(temperature, 1e-6)
        neg_sorted, order = _sort_with_order(-scaled)  # stable: ties -> low id
        sorted_logits = -neg_sorted
    with jax.named_scope("cake.sample.top_p"):
        rank = jnp.arange(v, dtype=jnp.int32)
        # top-p mass is measured on the top-k-truncated RENORMALIZED
        # distribution, matching sample_top_k_top_p's softmax-within-top-k
        # (with top_k >= V the where is identity, so pure top-p matches too)
        probs = jax.nn.softmax(
            jnp.where(rank < top_k, sorted_logits, -jnp.inf))
        prev_mass = jnp.cumsum(probs) - probs
        keep = (rank < top_k) & (prev_mass < top_p)
        keep = keep.at[0].set(True)                    # never mask every token
    with jax.named_scope("cake.sample.draw"):
        z = jnp.where(keep, sorted_logits, -jnp.inf) + _gumbel(rng, (v,))
        choice = order[jnp.argmax(z)]
        return jnp.where(temperature > 0.0, choice,
                         order[0]).astype(jnp.int32)


def config_has_filters(scfg: "SamplingConfig") -> bool:
    """True when `scfg` actually filters the vocabulary (top-k or
    top-p enabled) — the host-side gate for the verify programs' static
    `use_filters` escape hatch. Greedy and pure-temperature configs
    return False: their target distribution needs no sort."""
    return scfg.top_k is not None or (
        scfg.top_p is not None and scfg.top_p < 1.0)


def push_recent_token(recent_tokens, token):
    """Shift a new token into the device-resident recent-token ring
    (drives the repeat penalty without host round-trips)."""
    return jnp.concatenate([recent_tokens[1:], token.reshape(1)])


# -- speculative decoding: traced target distribution + acceptance rule ------


def filtered_probs(logits, temperature, top_k, top_p, repeat_penalty,
                   recent_tokens, use_filters: bool = True):
    """The target distribution p the sampled decode path draws from, as an
    explicit [V] probability vector in VOCAB order — the quantity the
    speculative accept/reject rule needs (sample_traced only ever needs the
    argmax of the gumbel-perturbed logits, so it never materializes p).

    Same traced pipeline as sample_traced: sign-aware repeat penalty,
    temperature, one descending sort serving the top-k rank mask and the
    top-p cumulative-mass mask measured on the top-k-renormalized
    distribution. temperature <= 0 degenerates to (almost) a point mass at
    the penalized argmax — ties split evenly, and downstream greedy
    consumers take jnp.argmax(p), which breaks ties to the lowest id
    exactly like sample_argmax.

    `use_filters` is a STATIC escape hatch for callers that know top_k
    and top_p are disabled for the whole dispatch (greedy and pure-
    temperature traffic — the serve engine's common case): the sort that
    serves the rank and cumulative-mass masks is skipped entirely and p
    is the plain penalized/tempered softmax. XLA's CPU sort is slow
    enough that it dominated the batched verify's accept rule; with
    filters disabled the masks are identity, so skipping the sort is
    exact (argmax and softmax are permutation-free)."""
    v = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    idx = jnp.where(recent_tokens < 0, v, recent_tokens)
    flagged = jnp.zeros((v,), jnp.bool_).at[idx].set(True, mode="drop")
    penalized = jnp.where(lf >= 0, lf / repeat_penalty, lf * repeat_penalty)
    lf = jnp.where(flagged, penalized, lf)
    scaled = lf / jnp.maximum(temperature, 1e-6)
    if not use_filters:
        return jax.nn.softmax(scaled)
    neg_sorted, order = _sort_with_order(-scaled)      # stable: ties -> low id
    sorted_logits = -neg_sorted
    rank = jnp.arange(v, dtype=jnp.int32)
    probs = jax.nn.softmax(jnp.where(rank < top_k, sorted_logits, -jnp.inf))
    prev_mass = jnp.cumsum(probs) - probs
    keep = (rank < top_k) & (prev_mass < top_p)
    keep = keep.at[0].set(True)                        # never mask every token
    kept = jnp.where(keep, probs, 0.0)
    kept = kept / jnp.maximum(jnp.sum(kept), 1e-30)
    return jnp.zeros((v,), jnp.float32).at[order].set(kept)


@jax.named_scope("cake.sample")
def spec_accept(logits, draft, n_draft, rng, temperature, top_k, top_p,
                repeat_penalty, recent_tokens, use_filters: bool = True):
    """Traced speculative accept/reject loop (Leviathan et al. 2023; Chen
    et al. 2023) for a DETERMINISTIC drafter (point-mass q — the n-gram
    drafter and the greedy draft-model drafter both are).

    logits: [S, V] verify-forward logits, row i = target distribution for
    the token following input i (S >= n_draft + 1); draft: [K] int32
    proposals, entries >= n_draft are padding; rng: consumed key.

    Greedy target (temperature <= 0): accept draft[i] iff it equals the
    penalized argmax — exact prefix match, so the emitted sequence is
    BIT-IDENTICAL to non-speculative greedy decoding. Sampled target: with
    q = delta at draft[i], the rejection rule accepts with probability
    min(1, p(x)/q(x)) = p(x) and on rejection resamples from the residual
    norm(max(0, p - q)) = p with x's mass removed — the marginal
    distribution of each emitted token is exactly p (p(x)*1 +
    (1-p(x)) * p(t)/(1-p(x)) = p(t)), so speculation never changes the
    output distribution, only the number of device steps.

    Returns (n_acc in [0, n_draft], next_token, recent') where next_token
    is the correction (rejection at position n_acc) or the bonus token
    (all n_draft accepted), and recent' has the accepted tokens AND
    next_token pushed — positions later in the same verify step see
    earlier accepted tokens in their repeat-penalty window, matching the
    one-token-at-a-time path.

    The rule is evaluated BATCHED, not as a sequential scan: row i's
    outcome only matters when every earlier draft accepted (acceptance
    is a prefix), so row i's target distribution may be computed under
    the assumption that drafts 0..i-1 were pushed into the penalty
    window — every row's filtered_probs runs in one vmap, the accepted
    prefix length falls out of a cumulative product, and the per-row
    penalty windows are a sliding gather over [recent ; draft]. A
    sequential fori_loop here cost ~1 ms/step on CPU (it serialized k
    sorts and k threefry folds) and dominated the whole batched-verify
    dispatch; the vectorized rule is shape-identical and draws the SAME
    per-row uniforms (fold_in(rng, i)), so outcomes are unchanged.

    `use_filters` (STATIC) mirrors filtered_probs': pass False when the
    caller knows every slot in the dispatch has top-k/top-p disabled and
    the per-row sorts vanish.
    """
    k = draft.shape[0]
    n = recent_tokens.shape[0]
    greedy = temperature <= 0.0
    # per-row penalty windows under the accepted-prefix assumption:
    # win[i] = [recent ; draft][i : i+n] (row i sees drafts 0..i-1)
    big = jnp.concatenate([recent_tokens, draft])
    win = big[jnp.arange(k + 1)[:, None] + jnp.arange(n)[None, :]]
    # S may be as small as n_draft + 1: clamp row gathers like the old
    # traced logits[i] indexing did (rows past S are never accepted)
    row = jnp.minimum(jnp.arange(k + 1), logits.shape[0] - 1)
    probs = jax.vmap(
        lambda lg, w: filtered_probs(lg, temperature, top_k, top_p,
                                     repeat_penalty, w,
                                     use_filters))(logits[row], win)
    idx = jnp.arange(k, dtype=jnp.int32)
    u = jax.vmap(lambda i: jax.random.uniform(jax.random.fold_in(rng, i)))(
        idx)
    p_draft = jnp.take_along_axis(probs[:k], draft[:, None], axis=1)[:, 0]
    ok = jnp.where(greedy, draft == jnp.argmax(probs[:k], axis=1),
                   u < p_draft)
    ok = ok & (idx < n_draft)
    # accepted prefix length: leading run of accepts
    n_acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32)))
    p = probs[n_acc]
    recent = win[n_acc]
    # rejected at n_acc: resample from the residual (p minus the rejected
    # point mass, renormalized); all accepted: plain sample from p
    rejected = n_acc < n_draft
    d_rej = draft[jnp.clip(n_acc, 0, k - 1)]
    resid = p.at[d_rej].set(jnp.where(rejected, 0.0, p[d_rej]))
    resid = resid / jnp.maximum(jnp.sum(resid), 1e-30)
    nxt = jnp.where(
        greedy, jnp.argmax(p),
        jax.random.categorical(jax.random.fold_in(rng, k),
                               jnp.log(jnp.maximum(resid, 1e-38)))
    ).astype(jnp.int32)
    return n_acc, nxt, push_recent_token(recent, nxt)
