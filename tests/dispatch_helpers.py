"""Helpers shared by the tests of the dispatch program.

``assert_paged_equals_cold`` is the bar the "paged == cold" pins hold on
the CPU. On a tiny llama, cold against warm through the same jitted
dispatch programs (scratch probe, PR 28): every PAGED slot of the final
cache is bitwise the cold prefill's (the gather returns what the insert
stored), and so is layer 0's K/V in the recompute-window slots (a
projection of the same embeddings). The first tensor that differs is
layer 1's K/V in the WINDOW slots, by 1 ulp of float32 (1.3e-7 on values
of order 1): the window rows' layer-0 attention runs as a W-row block of
``decoder.extend`` over the S-slot view where the cold prefill ran the
S-row quadratic pass — the same reduction over the same keys, which
XLA:CPU vectorizes differently for the two shapes. One more layer and
the softmax carry that to the readouts: log-probabilities move by 1 ulp
at their magnitude (4.8e-7 at ~5), probabilities by the same absolute
step of their logit (5e-7 relative), the weighted confidence by 1 ulp
(3.8e-6 at ~47). Tokens, top-2 and top-k ids never move. So: integers
exact, floats to 3e-6 relative (a few ulps of a logit below 16), the bar
``tests/test_cascade_decode.py::_assert_ulp_close`` set for the same
kind of cause in PR 17. Whether the chip's compiler tiles the two
shapes alike is PERF.md §7's to say.
"""

import numpy as np

from lir_tpu.engine import generate
from lir_tpu.engine.compile_plan import ShapeSpec

FUSED_FIELDS = ("generated", "p_yes", "p_no", "top2_ids", "topk_logprobs",
                "topk_ids", "weighted_confidence")


def assert_ulp_close(got, want, err_msg=""):
    """Integer arrays exact; float arrays within a few float32 ulps."""
    got, want = np.asarray(got), np.asarray(want)
    if got.dtype.kind in "iub":
        np.testing.assert_array_equal(got, want, err_msg=err_msg)
    else:
        np.testing.assert_allclose(got, want, rtol=3e-6, atol=3e-8,
                                   err_msg=err_msg)


def assert_paged_equals_cold(paged, cold):
    """Two FusedDecodeOut: tokens and decisions exact, floats to the ulp
    bar above."""
    for f in FUSED_FIELDS:
        assert_ulp_close(getattr(paged, f), getattr(cold, f),
                         err_msg=f"fused field {f}")


# ---------------------------------------------------------------------------
# The argument lists of the former entry points that tests called
# directly, spelled over the one dispatch program
# (generate.greedy_decode_dispatch), so that the parity tests written
# against them keep their cases unchanged. Each returns what its namesake
# returned.
# ---------------------------------------------------------------------------

def _stops(binary, digits, eos_id):
    if eos_id is None or (binary is None and digits is None):
        return None
    return generate.Stops(binary=binary, digits=digits, eos_id=eos_id)


def _call(params, cfg, program, args, scratch_cache, lower=False):
    fn = generate.greedy_decode_dispatch
    if lower:
        return fn.lower(params, cfg, program, args,
                        scratch_cache=scratch_cache)
    outs, specs, cache = fn(params, cfg, program, args,
                            scratch_cache=scratch_cache)
    out = tuple(outs) + (tuple(specs) if specs is not None else ())
    if program.return_cache:
        out += (cache,)
    return out if len(out) > 1 else out[0]


def fused_shared(params, cfg, prefix, prefix_mask, sfx_a, sfx_a_mask, sfx_b,
                 sfx_b_mask, yes_ids, no_ids, digit_ids, digit_vals,
                 max_new_a, max_new_b, topk=20, prefill_fn=None,
                 stop_mask_b=None, stop_mask_a=None, eos_id=None,
                 return_cache=False, decode_trunk=0, scratch_cache=None,
                 trunk_len=0, int8_qk=False, drafts=None, spec_k=0, ngram=2,
                 draft_cfg=None, lower=False, trunk_cache=None):
    """greedy_decode_fused_shared's list; the keyword tail selects the
    cascade front (``trunk_len``; with ``trunk_cache`` the front that
    takes the trunk as a value) and the speculative tail (``drafts`` +
    ``spec_k``). The paged fronts are driven through the engine
    (tests/test_prefix_cache.py, tests/test_dispatch_program.py)."""
    program = generate.Program(
        front=("cascade_held" if trunk_cache is not None
               else "cascade" if trunk_len else "prefill"),
        max_new=(max_new_a, max_new_b), topk=topk,
        trunk=trunk_len or decode_trunk, int8_qk=int8_qk, spec_k=spec_k,
        ngram=ngram, draft_cfg=draft_cfg, prefill_fn=prefill_fn,
        return_cache=return_cache)
    args = generate.DispatchArgs(
        prefix=prefix, prefix_mask=prefix_mask, sfx=(sfx_a, sfx_b),
        sfx_mask=(sfx_a_mask, sfx_b_mask), yes_ids=yes_ids, no_ids=no_ids,
        digit_ids=digit_ids, digit_vals=digit_vals,
        stops=_stops(stop_mask_a, stop_mask_b, eos_id), drafts=drafts,
        trunk_cache=trunk_cache)
    return _call(params, cfg, program, args, scratch_cache, lower)


def fused_shared_cascade(params, cfg, *rest, trunk_len, **kw):
    return fused_shared(params, cfg, *rest, trunk_len=trunk_len, **kw)


def _drafts(eight, draft_params=None):
    ctx_a, len_a, dr_a, dl_a, ctx_b, len_b, dr_b, dl_b = eight
    return generate.Drafts(ctx=(ctx_a, ctx_b), ctx_len=(len_a, len_b),
                           tokens=(dr_a, dr_b), lens=(dl_a, dl_b),
                           params=draft_params)


def fused_shared_spec(params, cfg, prefix, prefix_mask, sfx_a, sfx_a_mask,
                      sfx_b, sfx_b_mask, yes_ids, no_ids, digit_ids,
                      digit_vals, ctx_a, ctx_a_len, draft_a, draft_a_len,
                      ctx_b, ctx_b_len, draft_b, draft_b_len, max_new_a,
                      max_new_b, spec_k, draft_params=None, **kw):
    """greedy_decode_fused_shared_spec's list: (out_a, out_b, spec_a,
    spec_b[, cache])."""
    return fused_shared(
        params, cfg, prefix, prefix_mask, sfx_a, sfx_a_mask, sfx_b,
        sfx_b_mask, yes_ids, no_ids, digit_ids, digit_vals, max_new_a,
        max_new_b, spec_k=spec_k,
        drafts=_drafts((ctx_a, ctx_a_len, draft_a, draft_a_len, ctx_b,
                        ctx_b_len, draft_b, draft_b_len), draft_params),
        **kw)


# The six former spec constructors of engine/compile_plan.py.

def shared_spec(bucket, batch, sfx_a, sfx_b, new_tokens, conf_tokens,
                stops_armed, scratch, spec_k=0, spec_draft=False,
                decode_trunk=0, window=0, trunk=0, int8_qk=False):
    return ShapeSpec("shared", bucket, batch, 0, sfx_a, sfx_b, new_tokens,
                     conf_tokens, stops_armed, scratch, window=window,
                     spec_k=spec_k, spec_draft=spec_draft, trunk=trunk,
                     cascade_int8=int8_qk, decode_trunk=decode_trunk)


def shared_paged_spec(bucket, batch, window, *rest, **kw):
    return shared_spec(bucket, batch, *rest, window=window, **kw)


def shared_cascade_spec(bucket, batch, trunk, *rest, **kw):
    return shared_spec(bucket, batch, *rest, trunk=trunk, **kw)


def shared_cascade_paged_spec(bucket, batch, trunk, window, *rest, **kw):
    return shared_spec(bucket, batch, *rest, trunk=trunk, window=window,
                       **kw)


def grouped_spec(bucket, groups, batch, sfx, max_new, stops_armed, scratch,
                 window=0):
    return ShapeSpec("grouped", bucket, batch, groups, sfx, 0, max_new, 0,
                     stops_armed, scratch, window=window)


def grouped_paged_spec(bucket, groups, batch, window, *rest):
    return grouped_spec(bucket, groups, batch, *rest, window=window)


def plan_specs(engine, dispatches, new_tokens, conf_tokens, stops_armed,
               stream_shape=None):
    """The compile plan of a schedule as the sweep builds it: the engine
    routes every dispatch, the plan compiles what the routes may run."""
    from lir_tpu.engine import compile_plan

    routes = engine.route_plan(dispatches, new_tokens, conf_tokens,
                               stops_armed)
    return compile_plan.plan_specs(dispatches, routes, stream_shape)
