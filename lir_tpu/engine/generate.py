"""Greedy decoding with per-step logit capture.

The reference's measurement path is ``model.generate(max_new_tokens=50,
output_scores=True, return_dict_in_generate=True)`` followed by a scan of the
first 10 score tensors (compare_base_vs_instruct.py:251-278). Here that is one
jitted program: prefill the KV cache, then ``lax.scan`` 50 greedy steps,
stacking each step's fp32 logits. Fixed shapes throughout — the grid engine
batches ragged prompts by left-padding (decoder.mask_positions makes padding
a no-op).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..models import decoder
from ..models.registry import ModelConfig, T5Config
from ..models import encdec
from ..ops.flash_decode import decode_extent
from . import tokens as _tok


def cache_extent(cfg: ModelConfig, need: int, batch: int) -> int:
    """Slots a dispatch program allocates for a KV cache that must hold
    ``need`` of them (prefix edge + suffix edge + decode budget) at
    ``batch`` rows: the decode kernel's own rule
    (ops/flash_decode.decode_extent — an extent its key splits tile
    well, at most 32 masked slots above ``need``). EVERY program below
    sizes its cache through here and nowhere else, so the compile plan
    (which lowers these same functions), the donated handoff buffer and
    the dispatch can never disagree on the extent."""
    return decode_extent(need, batch, cfg.n_heads // cfg.n_kv_heads)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FusedDecodeOut:
    """Per-step readout captured inside the decode scan — everything the
    sweeps consume, WITHOUT materializing the (B, T_new, V) logit stack.

    At seq 256 / vocab 32k / 10 steps the full stack is ~50 MB of HBM
    traffic per batch; this struct is ~100 floats per row. The fused path is
    the production scorer; `greedy_decode` (full capture) remains for
    debugging and parity tests.
    """

    generated: jax.Array      # (B, T_new) int32
    p_yes: jax.Array          # (B, T_new) fp32 softmax prob of the yes id
    p_no: jax.Array           # (B, T_new) fp32
    top2_ids: jax.Array       # (B, T_new, 2) int32 — the top-2 match rule
    topk_logprobs: jax.Array  # (B, K) fp32 at position 0 (D6 log-prob map)
    topk_ids: jax.Array       # (B, K) int32
    weighted_confidence: jax.Array  # (B,) fp32 E[v] over digit ids at pos 0


def is_per_row_keys(key: jax.Array) -> bool:
    """True when ``key`` is a BATCH of PRNG keys (one stream per prompt
    row), under either key flavor: typed keys (jax.random.key — a key
    batch is shape (B,), scalar key shape ()) or legacy uint32 keys (a
    batch is (B, 2), a single key (2,))."""
    try:
        if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
            return key.ndim >= 1
    except TypeError:
        pass
    return getattr(key, "ndim", 1) == 2


def _small_readout(logits: jax.Array, yes_ids: jax.Array, no_ids: jax.Array):
    """(B, V) fp32 logits -> (p_yes, p_no, top2_ids): O(B*V) compute, O(B)
    output."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    l_yes = jnp.take_along_axis(logits, yes_ids[:, None], axis=1)[:, 0]
    l_no = jnp.take_along_axis(logits, no_ids[:, None], axis=1)[:, 0]
    p_yes = jnp.exp(l_yes - lse)
    p_no = jnp.exp(l_no - lse)
    _, top2 = lax.top_k(logits, 2)
    return p_yes, p_no, top2.astype(jnp.int32)


def _stepped(n: int, state0, emit, advance):
    """The loop of every sequential tail: read a step's emission off the
    pending logits, run the model on it, until ``n`` steps ran or every
    row is done.

    ``emit(t, state) -> (token (B,), record, state, stop)`` reads step
    t's emission and whatever is recorded per step (a pytree of (B, ...)
    rows), moves the rows' stop state, and says with the scalar ``stop``
    whether every row is done after it; ``advance(t, token, state) ->
    state`` is the model forward on that token (state holds the logits,
    the cache and its mask). The order is emit 0, advance 0, emit 1, ...;
    the loop ends BEFORE the first advance that ``stop`` makes needless,
    so a generous budget costs the steps the longest row needs.

    A ``lax.while_loop``, and not a scan whose steps skip the forward
    under a ``lax.cond``: the cache in ``state`` is updated where it lies
    by the layer loop (models/decoder._scan_blocks) only while nothing
    but loops carries it. Through a conditional XLA's copy insertion
    gives up on a program of a dispatch's size and copies each stacked
    cache side in and out of the branch — seen compiled for a v5e: two
    copies a side per LAYER at the compiler's default analysis budget
    (tests/test_tpu_compile.py holds the loops to none).

    Returns (records stacked (n, B, ...), rows past the end zero; the
    step the loop ended at: ``n``, or the first whose emission made every
    row done; the final state). One more ``emit`` than a scan would make
    runs after the n-th advance; its record is dropped."""
    i32 = jnp.int32

    def put(bufs, t, rec):
        return jax.tree.map(lambda b, r: b.at[t].set(r, mode="drop"),
                            bufs, rec)

    tok, rec, state, stop = emit(jnp.zeros((), i32), state0)
    bufs = put(jax.tree.map(
        lambda r: jnp.zeros((n,) + r.shape, r.dtype), rec), 0, rec)

    def body(c):
        t, tok, state, _, bufs = c
        state = advance(t, tok, state)
        tok, rec, state, stop = emit(t + 1, state)
        return t + 1, tok, state, stop, put(bufs, t + 1, rec)

    t_end, _, state, _, bufs = lax.while_loop(
        lambda c: (c[0] < n) & ~c[3], body,
        (jnp.zeros((), i32), tok, state, stop, bufs))
    return bufs, t_end, state


def _fused_tail(params, cfg: ModelConfig, logits0: jax.Array, cache,
                cache_mask0: jax.Array, pos0: jax.Array, slot0: int,
                yes_ids: jax.Array, no_ids: jax.Array, digit_ids: jax.Array,
                digit_vals: jax.Array, max_new_tokens: int, topk: int,
                stop_mask: jax.Array = None, eos_id: jax.Array = None,
                stop_mask2: jax.Array = None, stop_sel: jax.Array = None,
                decode_trunk: int = 0,
                ) -> Tuple[FusedDecodeOut, Tuple]:
    """The fused greedy scan shared by the full-prompt and shared-prefix
    paths: start from ``logits0`` (the first generated position), write
    generated k/v at cache slots ``slot0 + t``, capture the C13/D6 readouts
    in-scan. Returns (FusedDecodeOut, final cache).

    ``decode_trunk`` (static) marks the cache's leading shared-trunk
    slots on a shared-prefix dispatch: every decode step's trunk splits
    then run trunk-aware (cascade decode — decoder.decode_step), the
    trunk K/V streaming from HBM once per step instead of once per row.
    Gated by ``cfg.cascade_decode``; 0 keeps the flat kernel exactly.

    ``stop_mask`` ((V,) int32 surface-class bitmask from
    tokens.digit_stop_classes) + ``eos_id`` enable the confidence early
    stop: a row is DONE once it emits EOS, or once a standalone digit run
    (pure digit tokens opened at a word boundary) is followed by a
    non-gluing token — at that point the decoded text provably contains a
    complete ``\\b\\d+\\b`` integer, the only thing the confidence parse
    reads. Letter-glued digits ('1'+'st') neither open nor terminate a
    run, and transparent specials (empty decode) change nothing, so the
    stop NEVER nulls an answer the full budget would have parsed. Done
    rows emit EOS from the next step (so host-side EOS trimming ends their
    text at the stop point), and once EVERY row is done the loop ends
    (:func:`_stepped`) — a generous token budget then costs
    actual-response-length decode steps, not the worst case; the steps
    never run read as a scan that skipped their forward would have left
    them (EOS emitted, the last step's probabilities repeated). Per-step
    p_yes/p_no/top2 after a row's stop point reflect
    the EOS-fed model and must not be consumed (the sweep's confidence
    readout uses position 0 only).

    ``stop_mask2`` + ``stop_sel`` ((B,) bool) select a SECOND class table
    per row: rows where ``stop_sel`` is True read their emitted token's
    class from ``stop_mask2`` instead of ``stop_mask``. The prefix-group
    decode mixes both sweep formats in one batch and needs the binary
    rows on the EOS-only table while confidence rows run the digit stop.
    """
    early_stop = stop_mask is not None and eos_id is not None
    # Position-0 extras (first generated position): top-k logprob map +
    # weighted confidence.
    with jax.named_scope("lir.readout"):
        logp0 = logits0 - jax.scipy.special.logsumexp(
            logits0, axis=-1, keepdims=True)
        tk_vals, tk_ids = lax.top_k(logp0, topk)
        p_digits = jnp.exp(logp0[:, digit_ids])                # (B, K)
        mass = jnp.maximum(p_digits.sum(axis=-1), 1e-10)
        wconf = (p_digits * digit_vals[None, :]).sum(axis=-1) / mass

    B = logits0.shape[0]
    N = max_new_tokens

    def emit(t, state):
        logits, cache, cache_mask, done, digit_run, prev_ew = state
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        rec = _small_readout(logits, yes_ids, no_ids)
        if not early_stop:
            return nxt, (nxt,) + rec, state, jnp.zeros((), bool)
        tok = jnp.where(done, eos_id, nxt)
        cls = stop_mask[tok]
        if stop_mask2 is not None:
            cls = jnp.where(stop_sel, stop_mask2[tok], cls)
        pure = (cls & _tok.STOP_PURE) != 0
        prefix = (cls & _tok.STOP_PREFIX) != 0
        glue = (cls & _tok.STOP_STARTS_WORD) != 0
        ends_w = (cls & _tok.STOP_ENDS_WORD) != 0
        transp = (cls & _tok.STOP_TRANSPARENT) != 0
        done = done | (tok == eos_id) | (digit_run & ~glue & ~transp)
        # A standalone digit run opens on a pure-digit token at a word
        # boundary (space prefix, or previous token ended non-word —
        # position 0 starts at a boundary: prev_ew init False), extends
        # through unprefixed pure-digit tokens, and is spoiled by
        # anything else. Transparent tokens freeze all text state.
        digit_run = jnp.where(
            transp, digit_run,
            (pure & (prefix | ~prev_ew)) | (digit_run & pure & ~prefix))
        prev_ew = jnp.where(transp, prev_ew, ends_w)
        return (tok, (tok,) + rec,
                (logits, cache, cache_mask, done, digit_run, prev_ew),
                jnp.all(done))

    def advance(t, tok, state):
        _, cache, cache_mask, done, digit_run, prev_ew = state
        # The slot is marked only when the step runs, so an early-stopped
        # tail's final mask never calls an unwritten KV slot valid
        # (ADVICE r4; no caller reads that mask today).
        cache_mask = cache_mask.at[:, slot0 + t].set(1)
        logits, cache = decoder.decode_step(
            params, cfg, cache, tok, pos0 + t, slot0 + t, cache_mask,
            trunk_len=decode_trunk)
        return logits, cache, cache_mask, done, digit_run, prev_ew

    zeros_b = jnp.zeros((B,), bool)
    with jax.named_scope("lir.decode"):
        (gen, p_yes, p_no, top2), t_end, state = _stepped(
            N, (logits0, cache, cache_mask0, zeros_b, zeros_b, zeros_b),
            emit, advance)
        cache_f = state[1]

    with jax.named_scope("lir.readout"):
        if early_stop:
            # The steps after the one that made every row done never ran:
            # they read EOS and that step's probabilities, as their
            # pending logits never moved.
            tail = jnp.arange(N) > t_end
            last = jnp.clip(t_end, 0, N - 1)
            gen = jnp.where(tail[:, None], eos_id, gen)
            p_yes = jnp.where(tail[:, None], p_yes[last], p_yes)
            p_no = jnp.where(tail[:, None], p_no[last], p_no)
            top2 = jnp.where(tail[:, None, None], top2[last], top2)
        return FusedDecodeOut(
            generated=jnp.swapaxes(gen, 0, 1),
            p_yes=jnp.swapaxes(p_yes, 0, 1),
            p_no=jnp.swapaxes(p_no, 0, 1),
            top2_ids=jnp.swapaxes(top2, 0, 1),
            topk_logprobs=tk_vals,
            topk_ids=tk_ids,
            weighted_confidence=wconf,
        ), cache_f


@functools.partial(jax.jit,
                   static_argnames=("cfg", "max_new_tokens", "topk",
                                    "prefill_fn"))
def greedy_decode_fused(params, cfg: ModelConfig, tokens: jax.Array,
                        attn_mask: jax.Array, yes_ids: jax.Array,
                        no_ids: jax.Array, digit_ids: jax.Array,
                        digit_vals: jax.Array, max_new_tokens: int = 50,
                        topk: int = 20,
                        prefill_fn=None, stop_mask: jax.Array = None,
                        eos_id: jax.Array = None) -> FusedDecodeOut:
    """Greedy decode with the C13/D6 readouts fused into the scan.

    yes_ids/no_ids: (B,) per-row target token ids (rows of one batch may
    score different prompts with different target tokens). digit_ids/vals:
    the integer-token table for the weighted-confidence readout (pass empty
    arrays to skip: the gather on an empty axis is free). stop_mask/eos_id
    enable the confidence early stop (see _fused_tail).
    """
    B, S = tokens.shape
    T = cache_extent(cfg, S + max_new_tokens, B)
    pf = prefill_fn or decoder.prefill
    logits0, cache, pos0 = pf(params, cfg, tokens, attn_mask, T)
    cache_mask0 = jnp.pad(attn_mask, ((0, 0), (0, T - S)))
    out, _ = _fused_tail(params, cfg, logits0, cache, cache_mask0, pos0, S,
                         yes_ids, no_ids, digit_ids, digit_vals,
                         max_new_tokens, topk, stop_mask=stop_mask,
                         eos_id=eos_id)
    return out


@functools.partial(jax.jit, static_argnames=("cfg", "prefill_fn"))
def prefill_cache(params, cfg: ModelConfig, tokens: jax.Array,
                  attn_mask: jax.Array, prefill_fn=None):
    """PREFILL-ONLY pass: run the prompt, return the KV cache, decode
    nothing — the prefill-role dispatch of disaggregated serving
    (serve/migrate.py). ``tokens``/``attn_mask`` are (B, S)
    RIGHT-padded at the bucket extent, exactly the canonical
    slot == position layout the shared-prefix paths prefill with, and
    the cache is allocated at S slots: ``decoder.prefill`` computes
    every slot's k/v at the S-wide attention extent and pads the cache
    afterwards, so the page values extracted from this cache are
    BITWISE the values a full scoring dispatch of the same bucket would
    have inserted (pinned by tests/test_migrate.py) — which is what
    lets a decode replica resume from migrated pages identically to a
    colocated run."""
    pf = prefill_fn or decoder.prefill
    _, cache, _ = pf(params, cfg, tokens, attn_mask, tokens.shape[1])
    return cache


@functools.partial(jax.jit, static_argnames=("cfg",))
def greedy_decode_trunk(params, cfg: ModelConfig, tokens: jax.Array):
    """THE trunk program: the cache of a shared trunk, prefilled ONCE a
    call at one row and the trunk's exact extent, every slot real (slot
    t is position t) — what the ``"cascade"`` front computes inside its
    own program, as a value the ``"cascade_held"`` front takes instead
    (:func:`_front`), dispatch after dispatch. ``tokens`` is (1, t).
    Whatever ``decoder.prefill`` returns for the family: K/V, with a
    mixer its state and conv tail at the trunk's end, for layers that
    differ in kind the six leaves with no tail (models/mixed.py).

    Named as the dispatch programs are, so a reader of device time that
    matches ``jit_greedy_decode*`` keeps seeing all of a sweep's."""
    ones = jnp.ones(tokens.shape, jnp.int32)
    _, cache, _ = decoder.prefill(params, cfg, tokens, ones,
                                  tokens.shape[1])
    return cache


@jax.named_scope("lir.prefill")
def _paged_prefix(params, cfg: ModelConfig, paged: "PagedFront",
                  prefix_mask: jax.Array, total_len: int):
    """The paged replacement for the shared-prefill step, EXACT-LAYOUT:
    assemble the cached prefix KV from the page pool (models/paged.
    gather_slots over ``paged.slot_src`` (B, S)) and teacher-force the
    recompute WINDOW — slots [w0, w0 + R), each row's prefix tokens in
    that range RIGHT-padded into ``paged.rem``/``rem_mask`` (B, R) — via
    one chunked extension over the S-slot cache view (decoder.extend at
    start_index = ``paged.win_start``, a TRACED scalar: the window is
    anchored at the dispatch's longest real row, not the bucket edge, so
    rows shorter than the bucket never pay recompute FLOPs for pad slots
    — and the anchor varies per dispatch without retracing). A dispatch
    then pays prefill FLOPs for R tokens per row instead of the whole
    bucket. At one row over a cascade trunk (``total_len`` == the trunk:
    no tail pad) a warm trunk costs no quadratic recompute at all.

    The layout discipline is what buys parity with the unpaged path —
    every paged slot bitwise, the window's to the last bits a W-row
    extension and an S-row prefill may differ by
    (tests/test_prefix_cache.py):

    - the dispatch programs RIGHT-pad their prefixes (slot == token
      position, runner.decode_fused_shared), so a token's slot — and
      hence the reduction layout that computes its KV — is independent
      of its row's length: pages produced under any row back any later
      row sharing the prefix bitwise;
    - the window extension runs over an S-slot cache view — the exact
      attention extent the prefill's quadratic pass reduces over — and
      only afterwards is the cache padded out to ``total_len`` with
      zeros, exactly as prefill pads;
    - unfilled slots (a short row's tail, slots a cold row has no pages
      for) read the trash page's exact zeros; the unpaged prefill holds
      garbage pad-token k/v there instead, but both contribute exact
      0.0 through the masked softmax, so the difference is invisible.

    ``prefix_mask`` is the standard right-pad mask (B, S) — the SAME
    tensor the unpaged path computes. Returns the cache with
    [0, total_len) allocated and [0, S) populated — the drop-in analogue
    of ``prefill``'s cache output.
    """
    from ..models import paged as paged_mod

    decoder.refuse_recurrent(cfg, "the paged prefix path")
    S = prefix_mask.shape[1]
    cache = paged_mod.gather_slots(paged.pool, paged.slot_src)  # S slots
    _, cache, _ = decoder.extend(params, cfg, cache, paged.rem,
                                 paged.rem_mask, prefix_mask,
                                 paged.win_start)

    def pad_leaf(a):
        pad = [(0, 0)] * a.ndim
        pad[2] = (0, total_len - S)                         # time axis
        return jnp.pad(a, pad)

    return jax.tree.map(pad_leaf, cache)


# ---------------------------------------------------------------------------
# Speculative scoring decode (prompt-lookup / fleet drafting, fused verify)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SpecOut:
    """Per-branch speculative-decode accounting, read out host-side into
    profiling.SpecStats. ``drafted``/``accepted`` are (3,) int32 token
    counts by draft source — 0 = radix-tree continuation, 1 = n-gram
    prompt-lookup (including fallback filler), 2 = fleet draft model.
    ``chunks`` counts the verify forwards actually run; ``seq_steps``
    the forwards the sequential scan would have run on the same rows
    (its all-done early exit included), so chunks vs seq_steps IS the
    dispatch-reduction headline."""

    drafted: jax.Array    # (3,) int32
    accepted: jax.Array   # (3,) int32
    chunks: jax.Array     # () int32
    seq_steps: jax.Array  # () int32


def _stop_transition(emit, done, digit_run, prev_ew, stop_mask, eos_id):
    """One emission's stop-state transition — EXACTLY _fused_tail's rules
    (shared so the speculative scan's done/digit-run evolution can never
    drift from the sequential scan's)."""
    cls = stop_mask[emit]
    pure = (cls & _tok.STOP_PURE) != 0
    prefix = (cls & _tok.STOP_PREFIX) != 0
    glue = (cls & _tok.STOP_STARTS_WORD) != 0
    ends_w = (cls & _tok.STOP_ENDS_WORD) != 0
    transp = (cls & _tok.STOP_TRANSPARENT) != 0
    new_done = done | (emit == eos_id) | (digit_run & ~glue & ~transp)
    new_run = jnp.where(
        transp, digit_run,
        (pure & (prefix | ~prev_ew)) | (digit_run & pure & ~prefix))
    new_ew = jnp.where(transp, prev_ew, ends_w)
    return new_done, new_run, new_ew


def _spec_tail(params, cfg: ModelConfig, logits0: jax.Array, cache,
               cache_mask0: jax.Array, pos0: jax.Array, slot0: int,
               yes_ids: jax.Array, no_ids: jax.Array, digit_ids: jax.Array,
               digit_vals: jax.Array, max_new_tokens: int, topk: int,
               spec_k: int, ctx0: jax.Array, ctx0_len: jax.Array,
               draft_tokens: jax.Array, draft_len: jax.Array,
               stop_mask: jax.Array = None, eos_id: jax.Array = None,
               ngram: int = 2, draft_params=None, draft_cfg=None,
               dcache=None, decode_trunk: int = 0):
    """The speculative counterpart of :func:`_fused_tail`: instead of T
    sequential decode steps, scan up to T verify WINDOWS of ``spec_k``
    teacher-forced positions each — [pending emission, draft, draft, ...]
    — through ONE multi-query forward (decoder.verify_extend), then
    greedily accept the draft prefix the verifier's own argmax confirms.
    A window emits between 1 and spec_k tokens and consumes exactly
    spec_k cache slots (rejected tails stay mask-0 garbage, the
    early-stop discipline), so the cache is sized slot0 + T*spec_k.

    Parity contract (pinned by tests/test_spec_decode.py): every
    CONSUMED result is bitwise the sequential scan's, and the per-step
    float rows match within float tolerance —

    - an accepted draft is accepted BECAUSE it equals the verifier's
      argmax at that position, so the emitted token stream, the top-2
      stream, and every position-0 readout (target probabilities,
      top-20 logprob map, weighted confidence — the whole shared-path
      readout surface, sweep rows and serve payloads alike) are
      bitwise-identical; interior per-step probabilities come from the
      verify forward, whose logits are argmax-identical and
      tolerance-equal to decode_step's (decoder.verify_extend — the
      window cache's extra masked slots can regroup reduction lanes,
      the same bar PR-7's fused-vs-dense kernels cleared);
    - done rows advance on forced-EOS "drafts", reproducing the
      sequential scan's EOS-fed evolution, and once every row is done
      the positions past the global stop step are rewritten with the
      stop-step values — the sequential scan's all-done freeze,
      recovered exactly.

    Draft sources, per position (quality-only — a bad draft is simply
    rejected): the host-probed radix-tree continuation ``draft_tokens``
    (B, T) valid below ``draft_len``; an in-scan ``ngram``-gram lookup
    over ``ctx0`` (the row's compacted prompt, right-padded to
    ctx-width >= prompt + T) extended with accepted emissions; or, when
    ``draft_params`` is given, a fleet draft model running spec_k
    sequential small steps per window over its own ``dcache`` (same
    slot layout, same masks). Returns (FusedDecodeOut, final cache,
    final draft cache, SpecOut)."""
    assert spec_k >= 2, "speculation needs a draft window of >= 2"
    early = stop_mask is not None and eos_id is not None
    fleet = draft_params is not None
    T = max_new_tokens
    B = logits0.shape[0]
    W = ctx0.shape[1]

    # Position-0 extras — identical to _fused_tail.
    with jax.named_scope("lir.readout"):
        logp0 = logits0 - jax.scipy.special.logsumexp(
            logits0, axis=-1, keepdims=True)
        tk_vals, tk_ids = lax.top_k(logp0, topk)
        p_digits = jnp.exp(logp0[:, digit_ids])
        mass = jnp.maximum(p_digits.sum(axis=-1), 1e-10)
        wconf = (p_digits * digit_vals[None, :]).sum(axis=-1) / mass

    rows = jnp.arange(B)
    i32 = jnp.int32
    zeros_b = jnp.zeros((B,), bool)

    carry0 = dict(
        logits=logits0, cache=cache, cache_mask=cache_mask0,
        done=zeros_b, digit_run=zeros_b, prev_ew=zeros_b,
        filled=jnp.zeros((B,), i32), done_step=jnp.full((B,), T, i32),
        ctx=ctx0, ctx_n=ctx0_len.astype(i32),
        gen=jnp.zeros((B, T), i32),
        p_yes=jnp.zeros((B, T), jnp.float32),
        p_no=jnp.zeros((B, T), jnp.float32),
        top2=jnp.zeros((B, T, 2), i32),
        drafted=jnp.zeros((3,), i32), accepted=jnp.zeros((3,), i32),
        chunks=jnp.zeros((), i32),
    )
    if fleet:
        carry0["dcache"] = dcache

    def _scatter_row(buf, idx, val, ok):
        """Per-row scatter at (row, idx) where ``ok`` (dropped rows index
        out of range)."""
        eff = jnp.where(ok, idx, T)
        return buf.at[rows, eff].set(val, mode="drop")

    def _gather_ctx(ctx, idx):
        return jnp.take_along_axis(
            ctx, jnp.clip(idx, 0, W - 1)[:, None], axis=1)[:, 0]

    def go(carry):
        all_done = jnp.all(carry["done"])
        tstar = jnp.max(carry["done_step"])
        needed = jnp.where(all_done, jnp.minimum(T, tstar + 1), T)
        return jnp.min(carry["filled"]) < needed

    def _window(state):
        c, carry = state

        def run(carry):
            logits = carry["logits"]
            cache_mask = carry["cache_mask"]
            done = carry["done"]
            digit_run = carry["digit_run"]
            prev_ew = carry["prev_ew"]
            filled = carry["filled"]
            done_step = carry["done_step"]
            ctx, ctx_n = carry["ctx"], carry["ctx_n"]
            gen_b, py_b = carry["gen"], carry["p_yes"]
            pn_b, t2_b = carry["p_no"], carry["top2"]
            drafted, accepted = carry["drafted"], carry["accepted"]
            base = slot0 + c * spec_k
            live0 = filled < T
            done0 = done

            # -- emission 0: the pending token, from the carried logits.
            nxt = jnp.argmax(logits, axis=-1).astype(i32)
            e0 = jnp.where(done, eos_id, nxt) if early else nxt
            py0, pn0, t20 = _small_readout(logits, yes_ids, no_ids)
            gen_b = _scatter_row(gen_b, filled, e0, live0)
            py_b = _scatter_row(py_b, filled, py0, live0)
            pn_b = _scatter_row(pn_b, filled, pn0, live0)
            t2_b = _scatter_row(t2_b, filled, t20, live0)
            if early:
                nd, nr, ne = _stop_transition(e0, done, digit_run, prev_ew,
                                              stop_mask, eos_id)
                done_step = jnp.where(live0 & nd & ~done, filled, done_step)
                done = jnp.where(live0, nd, done)
                digit_run = jnp.where(live0, nr, digit_run)
                prev_ew = jnp.where(live0, ne, prev_ew)
            eff = jnp.where(live0, jnp.clip(ctx_n, 0, W - 1),
                            jnp.full((B,), W, i32))
            ctx = ctx.at[rows, eff].set(e0, mode="drop")
            ctx_n = ctx_n + live0.astype(i32)

            # -- drafts for window positions 1..spec_k-1 ------------------
            drafts, src_tree = [], []
            if fleet:
                dc = carry["dcache"]
                dm = cache_mask
                tok = e0
                for j in range(spec_k):
                    dm = lax.dynamic_update_slice(
                        dm, jnp.ones((B, 1), dm.dtype), (0, base + j))
                    dl, dc = decoder.decode_step(
                        draft_params, draft_cfg, dc, tok,
                        pos0 + filled + j, base + j, dm)
                    if j < spec_k - 1:
                        d = jnp.argmax(dl, axis=-1).astype(i32)
                        if early:
                            d = jnp.where(done, eos_id, d)
                        drafts.append(d)
                        src_tree.append(jnp.zeros((B,), bool))
                        tok = d
                new_dcache = dc
            else:
                # n-gram pattern: the last `ngram` context tokens
                # (prompt + emissions, e0 included).
                n_pos = W - ngram + 1
                pidx = jnp.arange(n_pos)
                eq = jnp.ones((B, n_pos), bool)
                for m in range(ngram):
                    pat_m = _gather_ctx(ctx, ctx_n - ngram + m)
                    eq = eq & (ctx[:, m:m + n_pos] == pat_m[:, None])
                ok_pos = (pidx[None, :] + ngram <= ctx_n[:, None] - 1)
                ok_pos = ok_pos & (ctx_n >= ngram)[:, None]
                best = jnp.where(eq & ok_pos, pidx[None, :], -1).max(axis=1)
                for j in range(1, spec_k):
                    t_idx = filled + j
                    tval = jnp.take_along_axis(
                        draft_tokens, jnp.clip(t_idx, 0, T - 1)[:, None],
                        axis=1)[:, 0]
                    t_ok = t_idx < draft_len
                    ng_idx = best + ngram + (j - 1)
                    ngval = _gather_ctx(ctx, ng_idx)
                    ng_ok = (best >= 0) & (ng_idx < ctx_n)
                    d = jnp.where(t_ok, tval,
                                  jnp.where(ng_ok, ngval, jnp.zeros((), i32)))
                    if early:
                        d = jnp.where(done, eos_id, d)
                    drafts.append(d)
                    src_tree.append(t_ok & ~done)

            # -- ONE fused verify over [e0, drafts...] --------------------
            X = jnp.stack([e0] + drafts, axis=1)           # (B, spec_k)
            cm_run = lax.dynamic_update_slice(
                cache_mask, jnp.ones((B, spec_k), cache_mask.dtype),
                (0, base))
            V, new_cache = decoder.verify_extend(
                params, cfg, carry["cache"], X, cm_run, base,
                trunk_len=decode_trunk)

            # -- greedy acceptance + per-position emissions ---------------
            acc = live0
            n_new = live0.astype(i32)
            d_state, r_state, e_state = done, digit_run, prev_ew
            for j in range(1, spec_k):
                Vj = V[:, j - 1]
                rj = jnp.argmax(Vj, axis=-1).astype(i32)
                if early:
                    rj = jnp.where(d_state, eos_id, rj)
                can = acc & (filled + j < T)
                ok = can & (X[:, j] == rj)
                pyj, pnj, t2j = _small_readout(Vj, yes_ids, no_ids)
                gen_b = _scatter_row(gen_b, filled + j, rj, ok)
                py_b = _scatter_row(py_b, filled + j, pyj, ok)
                pn_b = _scatter_row(pn_b, filled + j, pnj, ok)
                t2_b = _scatter_row(t2_b, filled + j, t2j, ok)
                eff = jnp.where(ok, jnp.clip(ctx_n - 1 + j, 0, W - 1),
                                jnp.full((B,), W, i32))
                ctx = ctx.at[rows, eff].set(rj, mode="drop")
                if early:
                    nd, nr, ne = _stop_transition(rj, d_state, r_state,
                                                  e_state, stop_mask, eos_id)
                    done_step = jnp.where(ok & nd & ~d_state, filled + j,
                                          done_step)
                    d_state = jnp.where(ok, nd, d_state)
                    r_state = jnp.where(ok, nr, r_state)
                    e_state = jnp.where(ok, ne, e_state)
                counted = can & ~done0
                if fleet:
                    drafted = drafted.at[2].add(jnp.sum(counted, dtype=i32))
                    accepted = accepted.at[2].add(
                        jnp.sum(ok & ~done0, dtype=i32))
                else:
                    tr = src_tree[j - 1]
                    drafted = drafted.at[0].add(
                        jnp.sum(counted & tr, dtype=i32))
                    drafted = drafted.at[1].add(
                        jnp.sum(counted & ~tr, dtype=i32))
                    accepted = accepted.at[0].add(
                        jnp.sum(ok & ~done0 & tr, dtype=i32))
                    accepted = accepted.at[1].add(
                        jnp.sum(ok & ~done0 & ~tr, dtype=i32))
                n_new = n_new + ok.astype(i32)
                acc = ok

            # Next pending logits = after the LAST emitted token.
            last = jnp.clip(n_new - 1, 0, spec_k - 1)
            nl = jnp.take_along_axis(V, last[:, None, None], axis=1)[:, 0]
            new_logits = jnp.where(live0[:, None], nl, logits)
            # Shrink the window's validity to the emitted prefix.
            cols = (jnp.arange(spec_k)[None, :]
                    < n_new[:, None]).astype(cache_mask.dtype)
            new_mask = lax.dynamic_update_slice(cm_run, cols, (0, base))
            ctx_n = ctx_n + (n_new - live0.astype(i32))

            out = dict(carry)
            out.update(logits=new_logits, cache=new_cache,
                       cache_mask=new_mask, done=d_state,
                       digit_run=r_state, prev_ew=e_state,
                       filled=filled + n_new, done_step=done_step,
                       ctx=ctx, ctx_n=ctx_n, gen=gen_b, p_yes=py_b,
                       p_no=pn_b, top2=t2_b, drafted=drafted,
                       accepted=accepted,
                       chunks=carry["chunks"] + jnp.ones((), i32))
            if fleet:
                out["dcache"] = new_dcache
            return out

        return c + 1, run(carry)

    # Windows run while some row still owes tokens, at most T of them: a
    # while loop, so that nothing but loops carries the caches (see
    # :func:`_stepped` on what a lax.cond around the forward costs).
    with jax.named_scope("lir.decode"):
        _, carry = lax.while_loop(
            lambda state: (state[0] < T) & go(state[1]), _window,
            (jnp.zeros((), i32), carry0))

    with jax.named_scope("lir.readout"):
        gen_b, py_b = carry["gen"], carry["p_yes"]
        pn_b, t2_b = carry["p_no"], carry["top2"]
        if early:
            # The sequential scan's all-done freeze: once EVERY row is done
            # (global stop step t*), it skips the model forward and repeats
            # the t*-step values to the end of the budget. Recover exactly
            # that tail from the evolved buffers.
            all_done = jnp.all(carry["done"])
            tstar = jnp.max(carry["done_step"])
            fr = jnp.clip(tstar, 0, T - 1)
            pos = jnp.arange(T)[None, :]
            tail = all_done & (pos > tstar)
            gen_b = jnp.where(tail, eos_id, gen_b)
            py_b = jnp.where(tail, py_b[:, fr][:, None], py_b)
            pn_b = jnp.where(tail, pn_b[:, fr][:, None], pn_b)
            t2_b = jnp.where(tail[..., None], t2_b[:, fr][:, None, :], t2_b)
            seq_steps = jnp.where(all_done, jnp.minimum(tstar, T),
                                  jnp.full((), T, i32)).astype(i32)
        else:
            seq_steps = jnp.full((), T, i32)

        out = FusedDecodeOut(
            generated=gen_b, p_yes=py_b, p_no=pn_b, top2_ids=t2_b,
            topk_logprobs=tk_vals, topk_ids=tk_ids, weighted_confidence=wconf)
    spec = SpecOut(drafted=carry["drafted"], accepted=carry["accepted"],
                   chunks=carry["chunks"], seq_steps=seq_steps)
    return out, carry["cache"], carry.get("dcache"), spec


# ---------------------------------------------------------------------------
# The dispatch program: front -> layout -> branches
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Program:
    """The ONE static description of a dispatch program
    (:func:`greedy_decode_dispatch`): which front fills the prefix's
    cache, how member rows lie on it, which tail decodes. Hashable, so
    it is the jit's static argument and, with the model config, all
    that selects a lowering beside the arguments' shapes.

    ``front`` — how slots [0, S) of the cache come to be:
    ``"prefill"`` (``prefill_fn`` or decoder.prefill over the prefix
    tokens), ``"paged"`` (:func:`_paged_prefix`: page gather + window
    extension; binds slot tables, not tokens), ``"cascade"`` (the first
    ``trunk`` tokens, which every row shares, prefilled ONCE at batch 1,
    the per-row remainders extended over them by decoder.cascade_extend),
    ``"cascade_paged"`` (that trunk resumed from the page pool at one
    row instead) or ``"cascade_held"`` (that trunk's cache handed in,
    ``DispatchArgs.trunk_cache``: :func:`greedy_decode_trunk` ran it once
    and every dispatch of the call that starts with it reads it).
    ``layout`` — ``"pair"``: two format branches on one cache, B from
    ``decoder.rewind`` of A's; ``"grouped"``: member rows gathered from
    G prefix rows by ``group_idx``, one branch with per-row stop tables.
    ``spec_k`` — 0: the sequential tail (:func:`_fused_tail`); >= 2: the
    draft-and-verify tail (:func:`_spec_tail`) at that window, with
    ``ngram`` and, for a fleet draft model, ``draft_cfg``.
    ``trunk`` — the leading slots every row shares: the extent a cascade
    front splits at, and for every front the extent the decode steps
    dedup at (0: flat kernels).
    ``max_new`` — one decode budget per branch."""

    front: str = "prefill"
    layout: str = "pair"
    max_new: Tuple[int, ...] = (50, 50)
    topk: int = 20
    trunk: int = 0
    int8_qk: bool = False
    spec_k: int = 0
    ngram: int = 2
    draft_cfg: Any = None
    prefill_fn: Any = None
    return_cache: bool = False


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedFront:
    """What a paged front resumes from: the page pool's leaves, each
    row's source slot per cache slot (G, S), and the recompute window —
    slots [win_start, win_start + R) with the rows' tokens there
    right-padded into ``rem``/``rem_mask`` (G, R)."""

    pool: Any
    slot_src: jax.Array
    win_start: jax.Array
    rem: jax.Array
    rem_mask: jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Stops:
    """The early stop's tables (:func:`_fused_tail`): ``binary`` the
    EOS-only classes, ``digits`` the digit-run classes, (V,) int32 each.
    A pair gives branch A the first and branch B the second; a grouped
    batch gives every row ``binary`` but those where ``sel`` (M,) bool
    is set, which read ``digits``."""

    binary: jax.Array
    digits: jax.Array
    eos_id: jax.Array
    sel: Any = None


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Drafts:
    """A speculative tail's drafting inputs, one entry per branch:
    ``ctx`` the compacted prompts (B, S + S2 + budget) valid below
    ``ctx_len``; ``tokens`` the host-probed continuations (B, budget)
    valid below ``lens``; ``params`` a fleet draft model's weights."""

    ctx: Tuple[jax.Array, ...]
    ctx_len: Tuple[jax.Array, ...]
    tokens: Tuple[jax.Array, ...]
    lens: Tuple[jax.Array, ...]
    params: Any = None


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DispatchArgs:
    """Every dynamic argument of :func:`greedy_decode_dispatch`, one
    pytree; a part the program does not take is None and so is absent
    from the traced tree. Prefix rows are (G, S) RIGHT-padded (slot ==
    token position); ``sfx``/``sfx_mask`` hold one RIGHT-padded (M, S2)
    suffix per branch; ``yes_ids``/``no_ids`` are per member row."""

    prefix_mask: jax.Array
    sfx: Tuple[jax.Array, ...]
    sfx_mask: Tuple[jax.Array, ...]
    yes_ids: jax.Array
    no_ids: jax.Array
    digit_ids: jax.Array
    digit_vals: jax.Array
    prefix: Any = None        # tokens; None on the "paged" front
    paged: Any = None         # PagedFront on the paged fronts
    trunk_cache: Any = None   # the held trunk's cache ("cascade_held");
    #                           read, never donated: later dispatches too
    group_idx: Any = None     # (M,) member row -> prefix row, grouped
    stops: Any = None
    drafts: Any = None


def dispatch_extent(cfg: ModelConfig, bucket: int, sfx: Sequence[int],
                    max_new: Sequence[int], rows: int,
                    spec_k: int = 0) -> int:
    """Cache slots a dispatch program allocates: the prefix edge plus the
    widest branch's suffix edge and decode region — one slot a step, or
    ``spec_k`` a verify window (rejected tails stay masked) — through
    :func:`cache_extent` at ``rows`` member rows."""
    width = spec_k or 1
    return cache_extent(
        cfg, bucket + max(s + n * width for s, n in zip(sfx, max_new)), rows)


def _front(params, cfg: ModelConfig, program: Program, args: DispatchArgs,
           total_len: int):
    """The cache with [0, total_len) allocated and the prefix's slots
    [0, S) filled, by ``program.front``; beside it a fleet draft
    model's cache of the same layout, or None."""
    a = args
    front = program.front
    fleet = a.drafts is not None and a.drafts.params is not None
    if fleet and front != "prefill":
        raise ValueError(f"a fleet draft model needs prefix tokens to "
                         f"prefill from; the {front!r} front has none")
    if front == "prefill":
        pf = program.prefill_fn or decoder.prefill
        _, cache, _ = pf(params, cfg, a.prefix, a.prefix_mask, total_len)
        dcache = None
        if fleet:
            _, dcache, _ = decoder.prefill(a.drafts.params,
                                           program.draft_cfg, a.prefix,
                                           a.prefix_mask, total_len)
        return cache, dcache
    if front == "paged":
        return _paged_prefix(params, cfg, a.paged, a.prefix_mask,
                             total_len), None
    # Cascade: row 0's first `trunk` tokens are byte-identical to every
    # other row's (the engine's LCP gate) and all real, so the trunk's
    # mask is all-ones and slot t is position t — the layout
    # cascade_extend assumes and the page pool stores. The quadratic
    # trunk prefill runs ONCE at batch 1 at the EXACT trunk extent (or
    # is gathered from the pool, or was handed in: no recompute at
    # all); the dense path pays it once per row.
    t = program.trunk
    ones = jnp.ones((1, t), a.prefix_mask.dtype)
    if front == "cascade":
        _, tcache, _ = decoder.prefill(params, cfg, a.prefix[:1, :t], ones,
                                       t)
    elif front == "cascade_paged":
        tcache = _paged_prefix(params, cfg, a.paged, ones, t)
    elif front == "cascade_held":
        tcache = a.trunk_cache
    else:
        raise ValueError(f"unknown front {front!r}")
    return decoder.cascade_extend(params, cfg, tcache, a.prefix[:, t:],
                                  a.prefix_mask[:, t:], t, total_len,
                                  int8_qk=program.int8_qk), None


def _extend_suffix(params, cfg: ModelConfig, cache, prefix_mask, sfx,
                   sfx_mask, at: int, total_len: int, view_len: int):
    """Teacher-force one branch's RIGHT-padded suffix into cache slots
    [at, at + S2) behind the prefix: the mask concat and the chunked
    extension (decoder.extend) of every branch of every program.
    Returns (first-position logits, cache, the branch's cache mask over
    ``total_len`` slots, next decode positions).

    The extension reduces over the first ``view_len`` slots only. A
    speculative cache is longer than the sequential one (spec_k slots a
    window); reduction lane grouping follows the attention extent, so
    its suffixes extend over a VIEW cut to the sequential extent, their
    k/v written back into the full cache afterwards — the position-0
    readouts then stay bitwise the sequential program's."""
    B, S = prefix_mask.shape
    S2 = sfx.shape[1]
    zeros = functools.partial(jnp.zeros, dtype=prefix_mask.dtype)
    gap = [zeros((B, at - S))] if at > S else []

    def mask(T):
        return jnp.concatenate(
            [prefix_mask] + gap + [sfx_mask, zeros((B, T - at - S2))],
            axis=1)

    cm = mask(total_len)
    if view_len == total_len:
        logits_l, cache2, pos = decoder.extend(params, cfg, cache, sfx,
                                               sfx_mask, cm, at)
        return logits_l, cache2, cm, pos
    view = jax.tree.map(
        lambda leaf: lax.slice_in_dim(leaf, 0, view_len, axis=2), cache)
    logits_l, view2, pos = decoder.extend(params, cfg, view, sfx, sfx_mask,
                                          mask(view_len), at)
    # Write only the suffix slots back — the extension touched nothing
    # else.
    cache2 = jax.tree.map(
        lambda full, v: lax.dynamic_update_slice_in_dim(
            full, lax.slice_in_dim(v, at, at + S2, axis=2), at, axis=2),
        cache, view2)
    return logits_l, cache2, cm, pos


def _start(params, cfg: ModelConfig, program: Program, args: DispatchArgs):
    """What every branch of a dispatch program starts from: the cache
    the front filled, laid out by member row (and a fleet draft model's
    beside it, or None), the member rows' prefix mask, and the two
    extents (the program's, and the sequential one a speculative cache
    is viewed at). The cache a program returns has these leaves' shapes
    whatever its tail does, so a plan that needs them to lower a donated
    variant (:func:`dispatch_cache_avals`) traces this much and no
    more."""
    a = args
    k = program.spec_k
    S = a.prefix_mask.shape[1]
    M = a.sfx[0].shape[0]
    widths = [s.shape[1] for s in a.sfx]
    T0 = dispatch_extent(cfg, S, widths, program.max_new, M, k)
    T_seq = dispatch_extent(cfg, S, widths, program.max_new, M)
    start, dstart = _front(params, cfg, program, a, T0)
    pm = a.prefix_mask
    if program.layout == "grouped":
        if k:
            raise ValueError("no speculative tail over per-row stop "
                             "tables (a grouped batch)")
        if cfg.layer_kinds:
            raise NotImplementedError(
                f"{cfg.name}: a grouped batch gathers member rows out of "
                "prefix rows, and a cache of layers that differ in kind is "
                "not laid out by row (models/mixed.py); the sweep plans "
                "none for such a model")
        from ..models import cache as cache_mod

        start = cache_mod.gather_rows(start, a.group_idx)
        pm = jnp.take(pm, a.group_idx, axis=0)                 # (M, S)
    elif program.layout != "pair":
        raise ValueError(f"unknown layout {program.layout!r}")
    return start, dstart, pm, T0, T_seq


def dispatch_cache_avals(params, cfg: ModelConfig, program: Program,
                         args: DispatchArgs):
    """Shapes and dtypes of the cache :func:`greedy_decode_dispatch`
    returns for these arguments: what its ``scratch_cache`` takes.
    Tracing only, and of the front alone: a whole trace of the program
    to learn its cache's avals made every donated variant cost two (a
    third of what loading one costs on a cache hit)."""
    return jax.eval_shape(
        lambda p, a: _start(p, cfg, program, a)[0], params, args)


@functools.partial(jax.jit, static_argnames=("cfg", "program"),
                   donate_argnames=("scratch_cache",), keep_unused=True)
def greedy_decode_dispatch(params, cfg: ModelConfig, program: Program,
                           args: DispatchArgs, scratch_cache=None):
    """THE dispatch program of the sweep and the server: every member
    row's fused greedy decodes behind ONE fill of the shared prefix.

    The perturbation sweep scores every grid cell under two formats
    whose prompts differ only in a short trailing instruction; the
    reference pays two full forward passes per cell. Here the shared
    prefix is filled once by ``program.front``, then each branch's
    suffix runs through a teacher-forced chunked extension at ~S2/S of
    the prefill cost (:func:`_extend_suffix`), followed by its tail.

    A later branch consumes the earlier branch's final cache buffer on
    purpose: the earlier suffix and generated slots are overwritten or
    masked (a branch's cache mask shows only prefix + its own suffix),
    so XLA aliases the cache update in place instead of holding two
    caches live. That is a rewind by mask, which K/V allows and a
    recurrent state does not: a model with a state-space mixer starts
    every branch from the SSM state and conv tail as they stood at the
    prefix's end (decoder.rewind: one held snapshot, one working copy).

    The last branch takes the digit table (weighted confidence); with
    stops armed, a pair's first branch takes the EOS-only table (its
    numeric readout is position 0 and its text is EOS-trimmed
    downstream) and its second the digit stop; a grouped batch selects
    per row (:class:`Stops`).

    Returns ``(outs, specs, cache)``: one FusedDecodeOut per branch; one
    SpecOut per branch from a speculative tail, else None; with
    ``program.return_cache`` the final cache, else None. ``scratch_cache``
    (DONATED) accepts a previous same-shape dispatch's returned cache so
    XLA writes this one into the same HBM block — one buffer per chain
    of a sweep instead of an alloc/free per dispatch
    (models/paged.CacheHandoff; programs of one shape return one cache
    aval whatever their front — for layers that differ in kind, one a
    trunk: compile_plan.handoff_key). Results never depend on its contents:
    the front overwrites every slot and attention is masked by the
    cache masks regardless."""
    del scratch_cache  # donated scratch: memory reuse only, never read
    a = args
    k = program.spec_k
    S = a.prefix_mask.shape[1]
    start, dstart, pm, T0, T_seq = _start(params, cfg, program, a)

    stops = a.stops
    outs, specs = [], []
    cache, dcache = start, dstart
    for i, (sfx, sfx_mask, budget) in enumerate(
            zip(a.sfx, a.sfx_mask, program.max_new)):
        if i:
            cache = decoder.rewind(cache, start)
            if dcache is not None:
                dcache = decoder.rewind(dcache, dstart)
        if i == len(a.sfx) - 1:
            d_ids, d_vals = a.digit_ids, a.digit_vals
        else:
            d_ids = jnp.zeros((0,), jnp.int32)
            d_vals = jnp.zeros((0,), jnp.float32)
        stop_kw = {}
        if stops is not None and stops.sel is not None:
            stop_kw = dict(stop_mask=stops.binary, stop_mask2=stops.digits,
                           stop_sel=stops.sel, eos_id=stops.eos_id)
        elif stops is not None:
            stop_kw = dict(stop_mask=(stops.binary, stops.digits)[i],
                           eos_id=stops.eos_id)
        slot0 = S + sfx.shape[1]
        logits_l, cache, cm, pos = _extend_suffix(
            params, cfg, cache, pm, sfx, sfx_mask, S, T0, T_seq)
        if not k:
            out, cache = _fused_tail(
                params, cfg, logits_l, cache, cm, pos, slot0, a.yes_ids,
                a.no_ids, d_ids, d_vals, budget, program.topk,
                decode_trunk=program.trunk, **stop_kw)
        else:
            dr = a.drafts
            if dcache is not None:
                _, dcache, _, _ = _extend_suffix(
                    dr.params, program.draft_cfg, dcache, pm, sfx,
                    sfx_mask, S, T0, T_seq)
            out, cache, dcache, spec = _spec_tail(
                params, cfg, logits_l, cache, cm, pos, slot0, a.yes_ids,
                a.no_ids, d_ids, d_vals, budget, program.topk, k,
                dr.ctx[i], dr.ctx_len[i], dr.tokens[i], dr.lens[i],
                ngram=program.ngram, draft_params=dr.params,
                draft_cfg=program.draft_cfg, dcache=dcache,
                decode_trunk=program.trunk, **stop_kw)
            specs.append(spec)
        outs.append(out)
    return (tuple(outs), tuple(specs) if k else None,
            cache if program.return_cache else None)

# ---------------------------------------------------------------------------
# Chunked prefill/decode piggybacking (Sarathi-Serve-style)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PiggybackCarry:
    """One in-flight shared dispatch, parked between engine calls with its
    decode scans still pending: the prefill + both suffix extensions have
    run, and the NEXT piggybacked call fuses this dispatch's decode scans
    into the same XLA program as its own prefill
    (:func:`shared_piggyback_step`) — the dispatch stream then pays one
    device round-trip per dispatch instead of a prefill call AND a decode
    drain, and the host gap between a decode scan and the next prefill
    disappears.

    Unlike the sequential path (branch B's suffix overwrites branch A's
    suffix slots after A's scan retires), a parked cache must keep BOTH
    branches alive, so the piggyback layout gives each branch a disjoint
    slot region: [S, S+S2a+max_new_a) for A, then B's suffix + decode
    region after it. Slots are physical only — positions, masks, and
    causality are all mask-aware — so per-row results are identical to
    the sequential dispatch (pinned by tests/test_kernels.py).
    """

    logits_a: jax.Array   # (B, V) fp32 — branch A first-position logits
    logits_b: jax.Array
    cache: Any            # KV cache pytree, branch regions disjoint
    cm_a: jax.Array       # (B, T) branch A cache mask (B region zeroed)
    cm_b: jax.Array
    pos_a: jax.Array      # (B,) next mask-aware decode positions
    pos_b: jax.Array


def _piggyback_extend(params, cfg: ModelConfig, prefix, prefix_mask,
                      sfx_a, sfx_a_mask, sfx_b, sfx_b_mask,
                      max_new_a: int, max_new_b: int,
                      prefill_fn=None) -> PiggybackCarry:
    """Prefill + both suffix extensions WITHOUT the decode scans, into the
    disjoint-region piggyback cache layout (see PiggybackCarry)."""
    decoder.refuse_recurrent(cfg, "the piggyback chain")
    B, S = prefix.shape
    S2a, S2b = sfx_a.shape[1], sfx_b.shape[1]
    T = cache_extent(cfg, S + S2a + max_new_a + S2b + max_new_b, B)
    pf = prefill_fn or decoder.prefill
    _, cache, _ = pf(params, cfg, prefix, prefix_mask, T)
    logits_a, cache, cm_a, pos_a = _extend_suffix(
        params, cfg, cache, prefix_mask, sfx_a, sfx_a_mask, S, T, T)
    logits_b, cache, cm_b, pos_b = _extend_suffix(
        params, cfg, cache, prefix_mask, sfx_b, sfx_b_mask,
        S + S2a + max_new_a, T, T)
    return PiggybackCarry(logits_a=logits_a, logits_b=logits_b, cache=cache,
                          cm_a=cm_a, cm_b=cm_b, pos_a=pos_a, pos_b=pos_b)


def _piggyback_scan(params, cfg: ModelConfig, carry: PiggybackCarry,
                    yes_ids, no_ids, digit_ids, digit_vals,
                    slot0_a: int, slot0_b: int, max_new_a: int,
                    max_new_b: int, topk: int, stop_mask_a, stop_mask_b,
                    eos_id) -> Tuple[FusedDecodeOut, FusedDecodeOut]:
    """Run the parked dispatch's two fused decode scans (branch A then B
    over the one carried cache buffer; B's mask excludes A's region, so
    per-row results equal the sequential dispatch's)."""
    empty_ids = jnp.zeros((0,), jnp.int32)
    empty_vals = jnp.zeros((0,), jnp.float32)
    out_a, cache_a = _fused_tail(params, cfg, carry.logits_a, carry.cache,
                                 carry.cm_a, carry.pos_a, slot0_a,
                                 yes_ids, no_ids, empty_ids, empty_vals,
                                 max_new_a, topk, stop_mask=stop_mask_a,
                                 eos_id=eos_id)
    out_b, _ = _fused_tail(params, cfg, carry.logits_b, cache_a,
                           carry.cm_b, carry.pos_b, slot0_b,
                           yes_ids, no_ids, digit_ids, digit_vals,
                           max_new_b, topk, stop_mask=stop_mask_b,
                           eos_id=eos_id)
    return out_a, out_b


@functools.partial(jax.jit,
                   static_argnames=("cfg", "max_new_a", "max_new_b",
                                    "prefill_fn"))
def shared_piggyback_prefill(params, cfg: ModelConfig, prefix, prefix_mask,
                             sfx_a, sfx_a_mask, sfx_b, sfx_b_mask,
                             max_new_a: int, max_new_b: int,
                             prefill_fn=None) -> PiggybackCarry:
    """Open a piggyback chain: dispatch the first shared batch's prefill +
    suffix extensions and park its decode scans in the returned carry."""
    return _piggyback_extend(params, cfg, prefix, prefix_mask, sfx_a,
                             sfx_a_mask, sfx_b, sfx_b_mask, max_new_a,
                             max_new_b, prefill_fn)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "max_new_a", "max_new_b", "topk",
                                    "prefill_fn"),
                   donate_argnames=("carry",))
def shared_piggyback_step(params, cfg: ModelConfig, carry: PiggybackCarry,
                          prefix, prefix_mask, sfx_a, sfx_a_mask, sfx_b,
                          sfx_b_mask, yes_ids, no_ids, digit_ids,
                          digit_vals, max_new_a: int, max_new_b: int,
                          topk: int = 20, stop_mask_a=None,
                          stop_mask_b=None, eos_id=None, prefill_fn=None):
    """One piggybacked call: the PARKED dispatch's pending decode scans and
    the NEXT dispatch's prefill + suffix extensions run in ONE XLA
    program. ``yes_ids``/``no_ids`` (and the stop tables) belong to the
    parked dispatch; the chain's shapes/budgets are identical by
    construction (the scheduler only chains same-shape dispatches), so
    the new carry reuses the donated old one's buffers. Returns
    (parked binary out, parked confidence out, new carry)."""
    B, S = prefix.shape
    S2a, S2b = sfx_a.shape[1], sfx_b.shape[1]
    out_a, out_b = _piggyback_scan(
        params, cfg, carry, yes_ids, no_ids, digit_ids, digit_vals,
        S + S2a, S + S2a + max_new_a + S2b, max_new_a, max_new_b, topk,
        stop_mask_a, stop_mask_b, eos_id)
    new_carry = _piggyback_extend(params, cfg, prefix, prefix_mask, sfx_a,
                                  sfx_a_mask, sfx_b, sfx_b_mask, max_new_a,
                                  max_new_b, prefill_fn)
    return out_a, out_b, new_carry


@functools.partial(jax.jit,
                   static_argnames=("cfg", "slot0_a", "slot0_b", "max_new_a",
                                    "max_new_b", "topk"),
                   donate_argnames=("carry",))
def shared_piggyback_drain(params, cfg: ModelConfig, carry: PiggybackCarry,
                           yes_ids, no_ids, digit_ids, digit_vals,
                           slot0_a: int, slot0_b: int, max_new_a: int,
                           max_new_b: int, topk: int = 20,
                           stop_mask_a=None, stop_mask_b=None, eos_id=None):
    """Close a piggyback chain: run the last parked dispatch's decode scans
    alone (no prefill rides along — the chain is over)."""
    return _piggyback_scan(params, cfg, carry, yes_ids, no_ids, digit_ids,
                           digit_vals, slot0_a, slot0_b, max_new_a,
                           max_new_b, topk, stop_mask_a, stop_mask_b,
                           eos_id)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "max_new_tokens", "prefill_fn"))
def greedy_decode(params, cfg: ModelConfig, tokens: jax.Array,
                  attn_mask: jax.Array, max_new_tokens: int = 50,
                  prefill_fn=None) -> Tuple[jax.Array, jax.Array]:
    """tokens/attn_mask: (B, S) LEFT-padded int32.

    Returns (generated (B, max_new_tokens) int32,
             step_logits (B, max_new_tokens, V) fp32)."""
    B, S = tokens.shape
    T = cache_extent(cfg, S + max_new_tokens, B)
    pf = prefill_fn or decoder.prefill
    logits0, cache, pos0 = pf(params, cfg, tokens, attn_mask, T)

    cache_mask0 = jnp.pad(attn_mask, ((0, 0), (0, T - S)))

    def step(carry, t):
        logits, cache, cache_mask = carry
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        cache_mask = cache_mask.at[:, S + t].set(1)
        new_logits, cache = decoder.decode_step(
            params, cfg, cache, nxt, pos0 + t, S + t, cache_mask)
        return (new_logits, cache, cache_mask), (nxt, logits)

    (_, _, _), (gen, step_logits) = lax.scan(
        step, (logits0, cache, cache_mask0), jnp.arange(max_new_tokens))
    # scan stacks on axis 0 -> (T_new, B, ...); put batch first.
    return jnp.swapaxes(gen, 0, 1), jnp.swapaxes(step_logits, 0, 1)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "max_new_tokens", "prefill_fn"))
def sample_decode(params, cfg: ModelConfig, tokens: jax.Array,
                  attn_mask: jax.Array, key: jax.Array,
                  temperature: float = 0.9, max_new_tokens: int = 50,
                  prefill_fn=None, eos_id: jax.Array = None) -> jax.Array:
    """Temperature sampling with the same prefill + lax.scan structure as
    greedy_decode, for the on-pod perturbation generator (the reference
    rephrases with temperature 0.9 via the Anthropic API,
    perturb_prompts.py:799-809; here the sampler runs on the local model).

    ``key`` is either one PRNG key (a fresh subkey per step; a row's draws
    then depend on its batch position) or per-row keys shaped (B, 2) — each
    row gets its own stream folded per step, so a row's sample depends ONLY
    on its key, not on which batch it rides in (resume-deterministic
    reasoning sweeps key rows by grid-cell identity).

    ``eos_id`` arms the HF-generate-parity stop: a row emits EOS fill
    after its first EOS (no post-EOS samples leak into text, matching the
    API/HF semantics the reference relies on), and once EVERY row is done
    the loop ends (:func:`_stepped`) — a generous session budget then
    costs actual response length. Non-done rows' draws are bit-identical
    to the unstopped sampler (the per-step keys never depend on doneness).

    Returns generated (B, max_new_tokens) int32. Per-step logits are not
    captured — rephrasings need text only, and dropping the (B, T, V) stack
    keeps HBM free for long sample runs."""
    B, S = tokens.shape
    T = cache_extent(cfg, S + max_new_tokens, B)
    per_row = is_per_row_keys(key)
    early = eos_id is not None
    pf = prefill_fn or decoder.prefill
    logits0, cache, pos0 = pf(params, cfg, tokens, attn_mask, T)
    cache_mask0 = jnp.pad(attn_mask, ((0, 0), (0, T - S)))

    if per_row:
        # (T, B, 2): row b's stream at step t = fold_in(keys[b], t).
        keys = jax.vmap(
            lambda t: jax.vmap(lambda k: jax.random.fold_in(k, t))(key)
        )(jnp.arange(max_new_tokens))
    else:
        keys = jax.random.split(key, max_new_tokens)

    def emit(t, state):
        logits, cache, cache_mask, done = state
        # The emit after the last step reads past the keys; the index
        # clamps and its draw is dropped.
        step_key = lax.dynamic_index_in_dim(keys, t, keepdims=False)
        scaled = logits / jnp.maximum(temperature, 1e-6)
        if per_row:
            nxt = jax.vmap(jax.random.categorical)(step_key, scaled)
        else:
            nxt = jax.random.categorical(step_key, scaled, axis=-1)
        nxt = nxt.astype(jnp.int32)
        if not early:
            return nxt, nxt, state, jnp.zeros((), bool)
        tok = jnp.where(done, eos_id, nxt)
        done = done | (tok == eos_id)
        return tok, tok, (logits, cache, cache_mask, done), jnp.all(done)

    def advance(t, tok, state):
        _, cache, cache_mask, done = state
        cache_mask = cache_mask.at[:, S + t].set(1)
        logits, cache = decoder.decode_step(
            params, cfg, cache, tok, pos0 + t, S + t, cache_mask)
        return logits, cache, cache_mask, done

    gen, t_end, _ = _stepped(
        max_new_tokens, (logits0, cache, cache_mask0, jnp.zeros((B,), bool)),
        emit, advance)
    if early:
        # Steps that never ran: every row was done, so they read EOS.
        gen = jnp.where((jnp.arange(max_new_tokens) > t_end)[:, None],
                        eos_id, gen)
    return jnp.swapaxes(gen, 0, 1)


@functools.partial(jax.jit, static_argnames=("cfg", "max_new_tokens"))
def t5_greedy_decode(params, cfg: T5Config, enc_tokens: jax.Array,
                     enc_mask: jax.Array, max_new_tokens: int = 50
                     ) -> Tuple[jax.Array, jax.Array]:
    """Encoder-decoder greedy decode (reference Seq2Seq branch,
    compare_base_vs_instruct.py:203-241).

    Re-runs the (tiny) decoder stack over a fixed (B, max_new) buffer each
    step — sequences here are ≤50 tokens so a KV cache buys nothing.
    Returns (generated (B, max_new), step_logits (B, max_new, V) fp32)."""
    B = enc_tokens.shape[0]
    enc_out = encdec.encode(params, cfg, enc_tokens, enc_mask)

    dec_buf0 = jnp.full((B, max_new_tokens + 1), cfg.decoder_start_token_id,
                        dtype=jnp.int32)
    mask0 = jnp.zeros((B, max_new_tokens + 1), jnp.int32).at[:, 0].set(1)

    def step(carry, t):
        dec_buf, mask = carry
        logits = encdec.decode(params, cfg, enc_out, enc_mask, dec_buf, mask)
        # Logits at the last valid position (= t).
        step_logits = jnp.take_along_axis(
            logits, t[None, None, None].repeat(B, 0), axis=1)[:, 0, :]
        nxt = jnp.argmax(step_logits, axis=-1).astype(jnp.int32)
        dec_buf = dec_buf.at[:, t + 1].set(nxt)
        mask = mask.at[:, t + 1].set(1)
        return (dec_buf, mask), (nxt, step_logits)

    (_, _), (gen, step_logits) = lax.scan(
        step, (dec_buf0, mask0), jnp.arange(max_new_tokens))
    return jnp.swapaxes(gen, 0, 1), jnp.swapaxes(step_logits, 0, 1)
