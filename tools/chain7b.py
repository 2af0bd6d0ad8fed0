"""Programmed-chain parameter trees at FULL model size.

Same trick as tools/tiny_checkpoints.build_chain_gpt2, scaled to 7B: all
attention and MLP matrices are ZERO (they still execute at full matmul
cost — timing is identical to real weights for a given dtype/quant mode),
token embeddings are one-hot basis vectors, and an untied lm_head encodes
a token -> (argmax_next, runner_up) transition table with +10/+5 margins.
The model's output text is then a designed pure function of the last
prompt token, at genuine 7B compute cost — which makes REAL-tokenizer,
real-content measurements possible on random-initialized infrastructure:
the digit early-stop bench needs responses that actually contain
standalone integers, and the rephraser bench needs responses the
numbered-list parser can score for yield (VERDICT r4 #4/#5).

Margins survive int8 weight-only quantization exactly (0/5/10 per column
quantize to 0/64/127 at scale 10/127) and dominate temperature-0.9
sampling (logit gap ~320 after the rmsnorm sqrt(D) gain)."""

from __future__ import annotations

import sys
from pathlib import Path
from typing import Dict, Tuple

import numpy as np

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def _chain_content_leaves(cfg, chain: Dict[int, Tuple[int, int]],
                          junk_next: int, junk_second: int):
    """(tok_embed, lm_head) numpy fp32 — the only value-bearing leaves of
    a chain tree: one-hot basis embeddings + the transition-table head.
    Shared by the host builder (chain_param_tree) and the on-device
    builder (ship_quantized_chain) so their designed outputs agree."""
    D, V = cfg.hidden_size, cfg.vocab_size
    basis: Dict[int, int] = {}
    for t in chain:
        basis[t] = len(basis)
    junk_axis = len(basis)
    assert junk_axis < D, "chain larger than hidden size"

    tok_embed = np.zeros((V, D), np.float32)
    tok_embed[:, junk_axis] = 4.0
    for t, b in basis.items():
        tok_embed[t, junk_axis] = 0.0
        tok_embed[t, b] = 4.0

    lm_head = np.zeros((D, V), np.float32)
    for t, (nxt, second) in chain.items():
        lm_head[basis[t], nxt] += 10.0
        lm_head[basis[t], second] += 5.0
    lm_head[junk_axis, junk_next] += 10.0
    lm_head[junk_axis, junk_second] += 5.0
    return tok_embed, lm_head


def _chain_layout(cfg, dtype, jnp, linear):
    """The decoder param layout (models/decoder.init_params flag cascade)
    with every big linear built by ``linear(*shape)`` — dense zeros on
    the host path, zero QuantTensors on the on-device path. Single source
    so the two chain builders cannot drift; the content leaves
    (tok_embed / lm_head) are attached by the callers."""
    D, H, K, hd, F, L = (cfg.hidden_size, cfg.n_heads, cfg.n_kv_heads,
                         cfg.head_dim, cfg.intermediate_size, cfg.n_layers)

    def zeros(*shape):
        return jnp.zeros(shape, dtype)

    layers = {
        "ln1": {"scale": jnp.ones((L, D), dtype)},
        "wq": linear(L, D, H * hd), "wk": linear(L, D, K * hd),
        "wv": linear(L, D, K * hd), "wo": linear(L, H * hd, D),
        "w_up": linear(L, D, F), "w_down": linear(L, F, D),
    }
    if not cfg.shared_block_ln:
        layers["ln2"] = {"scale": jnp.ones((L, D), dtype)}
    if cfg.norm == "layernorm":
        layers["ln1"]["bias"] = zeros(L, D)
        if "ln2" in layers:
            layers["ln2"]["bias"] = zeros(L, D)
    if cfg.gated_mlp:
        layers["w_gate"] = linear(L, D, F)
    if cfg.qkv_bias:
        layers["bq"] = zeros(L, H * hd)
        layers["bk"] = zeros(L, K * hd)
        layers["bv"] = zeros(L, K * hd)
    if cfg.attn_out_bias:
        layers["bo"] = zeros(L, D)
    if cfg.mlp_bias:
        layers["b_up"] = zeros(L, F)
        layers["b_down"] = zeros(L, D)

    params = {"layers": layers}
    if cfg.pos_embedding == "learned":
        params["pos_embed"] = zeros(cfg.max_seq_len + cfg.learned_pos_offset,
                                    D)
    if cfg.embedding_norm:
        params["embed_ln"] = {"scale": jnp.ones((D,), dtype),
                              "bias": zeros(D)}
    if cfg.final_norm:
        fl = {"scale": jnp.ones((D,), dtype)}
        if cfg.norm == "layernorm":
            fl["bias"] = zeros(D)
        params["final_ln"] = fl
    return params


def chain_param_tree(cfg, chain: Dict[int, Tuple[int, int]],
                     junk_next: int, junk_second: int, dtype=None):
    """Build the decoder param tree (models/decoder.init_params layout)
    realizing ``chain``; unlisted tokens all map to (junk_next,
    junk_second). cfg must have tie_embeddings=False."""
    import jax.numpy as jnp

    dtype = dtype or jnp.bfloat16
    assert not cfg.tie_embeddings, "chain tree needs an untied lm_head"
    tok_embed, lm_head = _chain_content_leaves(cfg, chain, junk_next,
                                               junk_second)
    params = _chain_layout(cfg, dtype, jnp,
                           linear=lambda *s: jnp.zeros(s, dtype))
    params["tok_embed"] = jnp.asarray(tok_embed, dtype)
    params["lm_head"] = jnp.asarray(lm_head, dtype)
    return params


def single_token_id(tokenizer, text: str) -> int:
    ids = tokenizer(text, add_special_tokens=False).input_ids
    assert len(ids) == 1, (text, ids)
    return int(ids[0])


def last_token_id(tokenizer, text: str) -> int:
    return int(tokenizer(text, add_special_tokens=False).input_ids[-1])


def vocab_word_pieces(tokenizer, n: int, taken) -> list:
    """First ``n`` distinct space-prefixed alpha vocab pieces not in
    ``taken`` — chain preamble/cycle words. Picked straight from the
    vocab because BPE word TAILS collide across words (' nearly' and
    ' roughly' both end in 'y')."""
    import re

    out = []
    for tid in range(len(tokenizer)):
        piece = tokenizer.convert_ids_to_tokens(tid)
        if re.fullmatch(r"Ġ[a-z]{3,}", piece or "") and tid not in taken:
            out.append(tid)
            if len(out) == n:
                return out
    raise SystemExit(f"vocab too small: found {len(out)}/{n} word pieces")


# The two production-sweep format strings the chain anchors on (their LAST
# token is each response's transition trigger). Shared by bench.py and
# earlystop_bench so the recorded headline and the early-stop study stay
# apples-to-apples: editing one side only would silently anchor the two
# chains on different tokens.
CHAIN_RESPONSE_FORMAT = "Respond with either Yes or No only please"
CHAIN_CONFIDENCE_FORMAT = "Give a confidence number from 0 to 100"

# The chain's measured-response constants, owned HERE so bench.py derives
# its printed "answer at decode step N" provenance and its per-row
# expected-confidence assertion from the same source that programs the
# weights — changing the answer step or value can then never silently
# desync the headline JSON from what the chain actually emits (ADVICE r5,
# bench.py:133). CHAIN_ANSWER_STEP is one-two steps PAST the
# corpus-median answer word position of 0-1 (SCALE.md "confidence decode
# budget"), i.e. a conservative stop point.
CHAIN_ANSWER_STEP = 3
CHAIN_CONFIDENCE_VALUE = 85


def confidence_chain(fast, response_format: str, confidence_format: str,
                     answer_step: int = CHAIN_ANSWER_STEP):
    """Transition table realizing the production sweep's two response
    shapes on tokenizer ``fast``: the binary prompt (ending in
    ``response_format``'s last token) answers " Yes."-style, and the
    confidence prompt (ending in ``confidence_format``'s last token)
    emits ``answer_step - 1`` non-digit preamble words, then the
    single-token integer " 85", then ".", then EOS — the shape the digit
    early stop (engine/tokens.digit_stop_classes) halts on, at the
    corpus-measured answer position (SCALE.md "confidence decode budget":
    median answer word 0-1 across 1,382 committed reference rows).

    Returns ``(chain, junk_next, junk_second)`` for
    :func:`chain_param_tree` / :func:`ship_quantized_chain`."""
    conf_anchor = last_token_id(fast, confidence_format)
    bin_anchor = last_token_id(fast, response_format)
    eos = fast.eos_token_id
    digit = single_token_id(fast, f" {CHAIN_CONFIDENCE_VALUE}")
    dot = single_token_id(fast, ".")
    yes = single_token_id(fast, " Yes")
    # Preamble words (never digits): emitted before the integer so the
    # stop has real work to do at answer-step > 0.
    taken = {conf_anchor, bin_anchor, eos, digit, dot, yes}
    # vocab_word_pieces returns exactly this many pieces or raises.
    pre = vocab_word_pieces(fast, max(answer_step - 1, 1), taken)
    chain = {}
    seq = [conf_anchor] + pre[:max(answer_step - 1, 0)] + [digit, dot, eos]
    for a, b in zip(seq, seq[1:]):
        chain.setdefault(a, (b, dot))
    chain[bin_anchor] = (yes, dot)
    chain.setdefault(yes, (dot, eos))
    chain[eos] = (eos, dot)
    cast = [conf_anchor, bin_anchor, eos, digit, dot, yes] + pre
    assert len(set(cast)) == len(cast), "chain token collision"
    return chain, dot, eos


def bucket_sized_words(fast, rng, target_tokens: int = 205):
    """(word list, words-per-text) sizing rephrased mains to land in the
    256-token bucket under tokenizer ``fast`` — corpus words are
    multi-piece in a small trained vocab, so a fixed word count would
    spill into the 512 bucket and OOM the measured batch."""
    from lir_tpu.data.prompts import WORD_MEANING_QUESTIONS

    words = sorted({w for q in WORD_MEANING_QUESTIONS for w in q.split()
                    if w.isalpha()})
    sample = " ".join(rng.choice(words) for _ in range(50))
    per_word = len(fast(sample, add_special_tokens=False).input_ids) / 50
    return words, max(int(target_tokens / per_word), 8)


def bench_setup(max_seq_len: int, smoke_name: str):
    """Shared 7B-chain bench scaffolding: build the offline BPE
    tokenizer, and pick the 7B preset (vocab rounded to 128)
    on an accelerator or a tiny smoke config on CPU. Returns
    (jax, dev, on_accel, fast, cfg, mode)."""
    import dataclasses
    import os

    import jax

    from tiny_checkpoints import build_bpe_tokenizer

    dev = jax.devices()[0]
    on_accel = dev.platform != "cpu"
    fast = build_bpe_tokenizer()
    vocab = (len(fast) + 127) // 128 * 128
    if on_accel:
        from tools.scale_validation import resolve_preset
        cfg = dataclasses.replace(
            resolve_preset("llama2_7b"), vocab_size=vocab,
            tie_embeddings=False, kv_cache_int8=True)
        mode = f"{cfg.name} int8-dyn+kvq8, real BPE tokenizer"
    else:
        print("# no accelerator: tiny CPU smoke variant")
        from lir_tpu.models.registry import ModelConfig
        cfg = ModelConfig(name=smoke_name, vocab_size=vocab,
                          hidden_size=64, n_layers=2, n_heads=4,
                          intermediate_size=128, max_seq_len=max_seq_len,
                          tie_embeddings=False)
        mode = "0.2M-smoke"
    return jax, dev, on_accel, fast, cfg, mode


def ship_quantized_chain(jax, dev, cfg, chain, junk_next, junk_second):
    """Assemble the dynamic-int8 chain tree DIRECTLY on the accelerator.

    Every layer matrix of a chain tree is zeros, and ``quant.quantize`` of
    a zero matrix is exactly ``q = 0`` with the zero-safe scale floor
    ``1e-8 / 127`` — so those QuantTensors are constructed on-device with
    no host build and no transfer. Only the content-bearing leaves
    (one-hot tok_embed bf16 + the transition-table lm_head, quantized
    weight-only on device like quantize_decoder_params does) ship over
    host to device: ~0.4 GiB instead of the full 6.7 GiB int8 tree, whose
    host quantize + transfer used to dominate bench start-up."""
    import jax.numpy as jnp

    from lir_tpu.models import quant

    assert not cfg.tie_embeddings, "chain tree needs an untied lm_head"
    tok_embed, lm_head = _chain_content_leaves(cfg, chain, junk_next,
                                               junk_second)
    dtype = jnp.bfloat16

    with jax.default_device(dev):
        def zq(*shape):
            # quantize(zeros) == zero payload + the 1e-8/127 scale floor
            # (quant.quantize); dynamic matches random_quantized_params.
            return quant.QuantTensor(
                q=jnp.zeros(shape, jnp.int8),
                scale=jnp.full(shape[:-2] + shape[-1:], 1e-8 / 127.0,
                               jnp.float32),
                dynamic=True)

        params = _chain_layout(cfg, dtype, jnp, linear=zq)
        params["tok_embed"] = jnp.asarray(tok_embed, dtype)
        params["lm_head"] = quant.quantize(jnp.asarray(lm_head, dtype))
    return params
