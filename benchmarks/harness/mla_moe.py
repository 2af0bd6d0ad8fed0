"""Operations and bytes Kimi-K2's two latent-attention kernels need, from
shapes alone: what ``readers.trace_kernel_roofline`` asks of a module that
sizes kernels (``CALLS``, ``window_calls``) for ``latent_prefill`` (the
expanded form: every head's query against per-head keys of
``qk_nope + qk_rope`` and values of ``v_dim``) and ``latent_decode`` (the
absorbed form: every head's query against the latent ``[c | k_pe]`` of a
shared trunk or of a row's prefix, the value its first ``kv_rank``
columns).

``spec`` is ``references/mla_moe.MLASpec``. The sizes come from the
traffic, as ``harness/sala.dispatch_shapes`` lays it out (one trunk a
prompt a call: the trunk's own pass at one row, then the originals and
the rephrasing groups, which read that trunk and do not compute it), real
rows and tokens only.

``latent_prefill``, each call in a layer: the trunk over itself (causal,
one row; the trunk's own pass), the rows' own prefix tokens over the
trunk and themselves, and each format suffix over everything before it:
both products over every causal key, ``2 * heads * (qk + v)`` a (query,
key) pair; bytes the queries in and the outputs out (bfloat16), and each
distinct expanded key and value the call reads once (a shared trunk once
for all rows).

``latent_decode``, each call in a layer: one decode step's rows over the
main part (the trunk where the dispatch has one): ``2 * rows * heads *
keys * (kv_rank + qk_rope + kv_rank)``; bytes the latent read once for
each block of ``DECODE_ROWS`` rows (all rows one block where their number
is no multiple of it), the absorbed queries in (bfloat16) and the
float32 partials out. The rows' own slots are attended outside the
kernel and are not counted.
"""

from __future__ import annotations

from . import sala

DECODE_ROWS = 8       # ops/latent_attention.DECODE_ROWS: rows a decode program takes


def _keys(first: int, last: int) -> float:
    """Causal keys of the queries at positions first .. last - 1."""
    return (last * (last + 1) - first * (first + 1)) / 2.0


def _prefill(spec, queries: float, keys: float, distinct: float) -> tuple:
    H, qk, v = spec.heads, spec.qk_nope + spec.qk_rope, spec.v_dim
    flops = 2.0 * H * (qk + v) * keys
    nbytes = 2.0 * H * (qk + v) * queries + 2.0 * H * (qk + v) * distinct
    return flops, nbytes


def prefill_calls(spec, rows, shared, trunk, sfx, steps,
                  held=False) -> list:
    """Each ``latent_prefill`` call a dispatch needs in a layer."""
    rows, shared, trunk = int(rows), float(shared), int(trunk)
    calls = []
    if trunk and not held:
        calls.append(_prefill(spec, trunk, _keys(0, trunk), trunk))
    own = shared - trunk
    calls.append(_prefill(spec, rows * own, rows * _keys(trunk, shared),
                          trunk + rows * own))
    for s in sfx:
        calls.append(_prefill(spec, rows * s,
                              rows * _keys(shared, shared + s),
                              trunk + rows * (own + s)))
    return calls


def decode_calls(spec, rows, shared, trunk, sfx, steps) -> list:
    """Each ``latent_decode`` call: one a decode step a branch, over the
    trunk (or, without one, each row's whole prefix)."""
    rows = int(rows)
    H, dl, r = spec.heads, spec.kv_rank + spec.qk_rope, spec.kv_rank
    keys = float(trunk or shared)
    blocks = rows // DECODE_ROWS if rows % DECODE_ROWS == 0 else 1
    if not trunk:
        blocks = rows
    flops = 2.0 * rows * H * keys * (dl + r)
    nbytes = (2.0 * dl * keys * blocks + 2.0 * rows * H * dl
              + 4.0 * rows * H * (r + 2))
    return [(flops, nbytes)] * int(sum(steps))


CALLS = {"latent_prefill_call": prefill_calls,
         "latent_decode_call": decode_calls}


def window_calls(spec, mix: dict, prompts: list, perts: list,
                 steps=None) -> dict:
    """The prefill kernel reads every shape of dispatch; the decode kernel
    the shapes that decode. Empty for a model of another family."""
    if not hasattr(spec, "kv_rank"):
        return {}
    shapes = sala.dispatch_shapes(spec, mix, prompts, perts, steps)
    if not shapes:
        return {}
    decoding = [{k: v for k, v in s.items() if k != "held"}
                for s in shapes if any(s["steps"])]
    return {"latent_prefill_call": shapes, "latent_decode_call": decoding}
