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


# -- the vocabulary filter, in vocabulary order --------------------------------
#
# top-k and top-p both keep a PREFIX of the vocabulary ranked by (value
# descending, id ascending): a token stays while the weight strictly before it
# (1 a token for top-k, its probability for top-p) is below the target. A
# prefix is named by one value threshold and, where a run of tied values
# straddles the cut, one id threshold inside the run, and both are found by
# searching: each pass is a handful of masked sums over the row, where the
# sort this replaces was the largest device op of a decode step (PERF.md,
# PR 42).

_FAN_BITS = 3                  # a pass tries 2**bits - 1 thresholds at once
_MAX_PASSES = 32               # of one search; 32 bits need ceil(32/bits)
_KEY_LOW = -2 ** 31            # below the key of every float but a NaN


def _ordered_key(x):
    """int32 image of float32 in the same order (-0.0 joins +0.0, as the
    sort's comparator has it): what a search can halve bit by bit."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def _search(ok, lo, hi, need):
    """The smallest x in (lo, hi] with ok(x), for an ok that goes from false
    to true once as x grows; ok at lo is taken as false and at hi as true
    without being looked at. ok maps int32[m] candidates to their [m]
    answers in ONE pass over the row. Returns (x, passes).

    A while_loop whose predicate is the data's: with `need` false it runs
    no pass, and under vmap it runs while ANY row's interval is open."""
    m = (1 << _FAN_BITS) - 1
    j = jnp.arange(1, m + 1, dtype=jnp.uint32)

    def width(lo, hi):          # hi - lo, which 32 signed bits may not hold
        return jax.lax.bitcast_convert_type(hi - lo, jnp.uint32)

    def cond(c):
        lo, hi, passes = c
        return need & (width(lo, hi) > 1) & (passes < _MAX_PASSES)

    def body(c):
        lo, hi, passes = c
        w = width(lo, hi)
        # floor(w * j / 2**bits) without the product's overflow, kept inside
        off = (w >> _FAN_BITS) * j + (((w & m) * j) >> _FAN_BITS)
        x = lo + jax.lax.bitcast_convert_type(jnp.clip(off, 1, w - 1),
                                              jnp.int32)
        good = ok(x)
        return (jnp.max(jnp.where(good, lo, x)),
                jnp.min(jnp.where(good, x, hi)), passes + 1)

    _, hi, passes = jax.lax.while_loop(cond, body, (lo, hi, jnp.int32(0)))
    return hi, passes


def _prefix_cut(key, key_max, weight, target, need):
    """The prefix of (key descending, id ascending) order that is kept: a
    token while the weight strictly before it is < target, so every token
    above one value t and, of the run tied at t, the ids up to one id i.
    With `need` false everything is kept and no pass is made. Returns the
    mask and the two searches' pass counts."""
    v = key.shape[-1]
    ids = jnp.arange(v, dtype=jnp.int32)

    def weigh(mask):            # [m, V] masks to the [m] weights they hold
        return jnp.sum(jnp.where(mask, weight[None, :], 0.0), axis=-1)

    t, value_passes = _search(
        lambda x: weigh(key[None, :] > x[:, None]) < target,
        jnp.int32(_KEY_LOW), key_max, need)
    t = jnp.where(need, t, _KEY_LOW)
    # the run of tokens tied at the cut is kept lowest ids first; it is
    # searched only if it has a second token and does not fit whole
    tied = key == t
    before, run = weigh(jnp.stack([key > t, tied]))
    split = need & (jnp.sum(tied) > 1) & (before + run >= target)
    i, id_passes = _search(
        lambda x: before + weigh(tied[None, :] & (ids[None, :] <= x[:, None]))
        >= target, jnp.int32(-1), jnp.int32(v - 1), split)
    return (key > t) | (tied & (ids <= i)), (value_passes, id_passes)


def _keep_mask_and_passes(scaled, top_k, top_p):
    v = scaled.shape[-1]
    key = _ordered_key(scaled)
    top = jnp.max(scaled)
    key_max = _ordered_key(top)
    with jax.named_scope("cake.sample.top_k"):
        k = jnp.clip(top_k, 1, v)
        in_k, passes_k = _prefix_cut(key, key_max,
                                     jnp.ones((v,), jnp.float32),
                                     k.astype(jnp.float32), k < v)
    with jax.named_scope("cake.sample.top_p"):
        # top-p mass is measured on the top-k-truncated RENORMALIZED
        # distribution, matching sample_top_k_top_p's softmax-within-top-k;
        # the target is scaled by the sum and not each weight divided. A
        # target above 0 keeps the largest token whatever top_p says
        e = jnp.where(in_k, jnp.exp(scaled - top), 0.0)
        target = jnp.maximum(top_p * jnp.sum(e), jnp.finfo(jnp.float32).tiny)
        in_p, passes_p = _prefix_cut(key, key_max, e, target, top_p < 1.0)
    return in_k & in_p, passes_k + passes_p


def keep_mask(scaled, top_k, top_p):
    """Which of the scaled logits f32[V] the top-k / top-p filter keeps, as
    bool[V] in vocabulary order, with no sort:

    top_k: the top_k largest values, a tied run at the k-th value cut by
    LOWEST id first; top_k >= V keeps all. top_p: on the softmax over the
    top-k survivors, a token stays while the mass strictly before it in
    (value descending, id ascending) order is < top_p; the largest token
    always stays; top_p >= 1 keeps all. This is the set a stable descending
    sort with a rank mask and a running mass would keep, but that a mass is
    a masked sum here: a token whose preceding mass lies within float32
    rounding of top_p may fall on the other side.

    What is disabled costs no pass: each search is a while_loop whose
    predicate the data closes (top_k >= V, top_p >= 1, no tied run
    straddling a cut)."""
    with jax.named_scope("cake.sample.select"):
        return _keep_mask_and_passes(scaled, top_k, top_p)[0]


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

    Equivalence to the static `sample` dispatch. EXACT: temperature <= 0
    is sample_argmax after the same penalty, ties to the lowest id. IN
    DISTRIBUTION, not per key: the stochastic paths keep the set the static
    ones keep (keep_mask; the static top-p breaks a tied run at the cut by
    highest id) and draw gumbel noise over the whole vocabulary in
    vocabulary order, where the static ones draw it over the sorted prefix.

    The named scopes (obs.spans.SCOPE_CATALOG) are metadata for a device
    trace's reader: which of the parts the step's time is in.
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
    scaled = lf / jnp.maximum(temperature, 1e-6)
    sampled = temperature > 0.0
    # a greedy row reads no mask, so it asks for none: its searches are closed
    keep = keep_mask(scaled, jnp.where(sampled, top_k, v),
                     jnp.where(sampled, top_p, 1.0))
    with jax.named_scope("cake.sample.draw"):
        z = jnp.where(keep, scaled, -jnp.inf) + _gumbel(rng, (v,))
        return jnp.where(sampled, jnp.argmax(z),
                         jnp.argmax(scaled)).astype(jnp.int32)


def config_has_filters(scfg: "SamplingConfig") -> bool:
    """True when `scfg` actually filters the vocabulary (top-k or
    top-p enabled) — the host-side gate for the verify programs' static
    `use_filters` escape hatch. Greedy and pure-temperature configs
    return False: their target distribution keeps every token."""
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
    temperature, and keep_mask's set (top-k by rank, top-p by the mass
    before a token on the top-k-renormalized distribution), renormalized.
    temperature <= 0 degenerates to (almost) a point mass at the penalized
    argmax — ties split evenly, and downstream greedy consumers take
    jnp.argmax(p), which breaks ties to the lowest id exactly like
    sample_argmax.

    `use_filters` is a STATIC escape hatch for callers that know top_k
    and top_p are disabled for the whole dispatch (greedy and pure-
    temperature traffic — the serve engine's common case): p is the plain
    penalized/tempered softmax. keep_mask gives the same p there (it keeps
    everything and its searches make no pass); the hatch saves its few
    passes outside the searches."""
    v = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    idx = jnp.where(recent_tokens < 0, v, recent_tokens)
    flagged = jnp.zeros((v,), jnp.bool_).at[idx].set(True, mode="drop")
    penalized = jnp.where(lf >= 0, lf / repeat_penalty, lf * repeat_penalty)
    lf = jnp.where(flagged, penalized, lf)
    scaled = lf / jnp.maximum(temperature, 1e-6)
    if not use_filters:
        return jax.nn.softmax(scaled)
    return jax.nn.softmax(
        jnp.where(keep_mask(scaled, top_k, top_p), scaled, -jnp.inf))


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
    filters and k threefry folds) and dominated the whole batched-verify
    dispatch; the vectorized rule is shape-identical and draws the SAME
    per-row uniforms (fold_in(rng, i)), so outcomes are unchanged.

    `use_filters` (STATIC) mirrors filtered_probs': pass False when the
    caller knows every slot in the dispatch has top-k/top-p disabled.
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
