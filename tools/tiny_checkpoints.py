"""Deterministic tiny LOCAL HF checkpoints for executed-reference oracles.

The zero-egress image ships no pretrained weights, so every differential
that wants to run real HF code (ours AND the reference's staged scripts —
tools/reference_scorer_oracle.py) builds genuine checkpoints here: real
tokenizers (trained byte-BPE, constructed Unigram/Metaspace), real
`save_pretrained` safetensors, fixed torch seeds. The SAME builders back
the capture tool and the pytest differentials, so both sides always score
the identical weights (VERDICT r4 #1).

Builders:
- byte-BPE + GPT-2 (seed 0) — the GPT-2-style byte-level family
- Unigram/Metaspace + Llama (seed 1) — the sentencepiece family ("▁Yes")
- Unigram/Metaspace + T5 (seed 2) — the enc-dec branch
  (compare_base_vs_instruct.py:188-237)
- programmed-chain GPT-2 — a Markov-chain LM whose next token is a pure
  function of the current token (all attention/MLP weights zero, untied
  one-hot embeddings, +10/+5 logit margins). This gives EXACT control of
  where "Yes"/"No" first enters the top-2, so the reference's scan rule
  (compare_base_vs_instruct.py:264-285) is exercised at chosen positions
  1-9, as runner-up-of-top-2, and in the never-found position-0 fallback —
  outcomes random weights cannot pin.
- bos-adding Unigram/Metaspace + Llama — same pieces with a
  TemplateProcessing post-processor that prepends <s>, reproducing real
  llama tokenizers, to pin the reference's `tokenizer(" Yes").input_ids[0]`
  special-token grab (compare_base_vs_instruct.py:244-247) by execution.
"""

from __future__ import annotations

import sys
from pathlib import Path

_REPO = str(Path(__file__).resolve().parent.parent)
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _sp_tokenizer(add_bos: bool = False, with_pad: bool = False):
    """Unigram + Metaspace fast tokenizer (the llama/t5 scheme), built from
    the word-meaning corpus with explicit piece scores so resolution is
    deterministic."""
    import transformers as tf
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers
    from tokenizers.processors import TemplateProcessing

    from lir_tpu.data.prompts import WORD_MEANING_QUESTIONS

    corpus = list(WORD_MEANING_QUESTIONS) + [
        "Yes", "No", "Answer either 'Yes' or 'No'.",
        "Question: Answer:", "Is a tomato a vegetable?",
        "Give a confidence number from 0 to 100",
    ]
    words = sorted({w for line in corpus for w in line.split()})
    chars = sorted({c for line in corpus for c in line} | {"▁"})
    pieces = {"<unk>": 0.0, "<s>": 0.0, "</s>": 0.0}
    if with_pad:
        pieces["<pad>"] = 0.0       # T5 needs a real pad (reference
        # enc-dec branch tokenizes with padding=True, :194)
    for w in words:
        pieces.setdefault("▁" + w, -8.0)
    for v in range(101):
        pieces.setdefault("▁" + str(v), -8.0)
        pieces.setdefault(str(v), -9.0)
    for c in chars:
        pieces.setdefault(c, -12.0)
    tok = Tokenizer(models.Unigram(list(pieces.items()), unk_id=0))
    tok.pre_tokenizer = pre_tokenizers.Metaspace()
    tok.decoder = decoders.Metaspace()
    if add_bos:
        # Real LlamaTokenizer behavior: every encode() prepends <s>.
        bos_id = tok.token_to_id("<s>")
        tok.post_processor = TemplateProcessing(
            single="<s> $A", pair="<s> $A <s> $B",
            special_tokens=[("<s>", bos_id)])
    kw = {"pad_token": "<pad>"} if with_pad else {}
    return tf.PreTrainedTokenizerFast(
        tokenizer_object=tok, bos_token="<s>", eos_token="</s>",
        unk_token="<unk>", **kw)


def build_bpe_tokenizer():
    """Train the byte-level BPE tokenizer (real merges, real leading-space
    " Yes" semantics) — shared by the random and chain GPT-2 builders."""
    import transformers as tf
    from tokenizers import (Tokenizer, decoders, models, pre_tokenizers,
                            trainers)

    from lir_tpu.data.prompts import WORD_MEANING_QUESTIONS

    corpus = list(WORD_MEANING_QUESTIONS) + [
        "Yes", "No", " Yes", " No", "Answer either 'Yes' or 'No'.",
        "Question: Answer:", "Is a tomato a vegetable?",
        " ".join(str(i) for i in range(101)),
    ]
    tok = Tokenizer(models.BPE(unk_token=None))
    tok.pre_tokenizer = pre_tokenizers.ByteLevel(add_prefix_space=False)
    tok.decoder = decoders.ByteLevel()
    trainer = trainers.BpeTrainer(
        vocab_size=1024, special_tokens=["<|endoftext|>"],
        initial_alphabet=pre_tokenizers.ByteLevel.alphabet(),
        show_progress=False)    # its bar writes blank lines to stdout
    tok.train_from_iterator(corpus, trainer)
    return tf.PreTrainedTokenizerFast(
        tokenizer_object=tok, eos_token="<|endoftext|>")


def build_bpe_gpt2(path: Path):
    """Trained byte-level BPE tokenizer + random GPT-2 (seed 0) — byte-for-
    byte the construction tests/test_real_tokenizer_end_to_end.py uses."""
    import torch
    import transformers as tf

    fast = build_bpe_tokenizer()
    torch.manual_seed(0)
    # n_positions 512: the engine conservatively trims length buckets to
    # table_rows - max_new_tokens for learned-position models, and the
    # formatted few-shot prompts (~134 tokens) + a 50-token reference
    # generation budget need the 256 bucket to survive that trim.
    model = tf.GPT2LMHeadModel(tf.GPT2Config(
        vocab_size=len(fast), n_embd=64, n_layer=2, n_head=4,
        n_positions=512)).eval()
    path.mkdir(parents=True, exist_ok=True)
    model.save_pretrained(path, safe_serialization=True)
    fast.save_pretrained(path)
    return path, model, fast


def build_sp_llama(path: Path, add_bos: bool = False, seed: int = 1):
    """Unigram/Metaspace tokenizer + random Llama (seed 1) — byte-for-byte
    the tests/test_real_tokenizer_end_to_end.py construction; add_bos=True
    swaps in the bos-prepending variant (real-llama encode semantics)."""
    import torch
    import transformers as tf

    fast = _sp_tokenizer(add_bos=add_bos)
    torch.manual_seed(seed)
    model = tf.LlamaForCausalLM(tf.LlamaConfig(
        vocab_size=len(fast), hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=128,
        max_position_embeddings=256, tie_word_embeddings=False)).eval()
    path.mkdir(parents=True, exist_ok=True)
    model.save_pretrained(path, safe_serialization=True)
    fast.save_pretrained(path)
    return path, model, fast


def build_sp_t5(path: Path):
    """Unigram/Metaspace tokenizer + random tiny T5 (seed 2) for the
    enc-dec scorer branch (compare_base_vs_instruct.py:188-237: ids from
    tokenizer("Yes"), scores scanned from decoder steps)."""
    import torch
    import transformers as tf

    fast = _sp_tokenizer(with_pad=True)
    torch.manual_seed(2)
    model = tf.T5ForConditionalGeneration(tf.T5Config(
        vocab_size=len(fast), d_model=64, d_kv=16, d_ff=128,
        num_layers=2, num_decoder_layers=2, num_heads=4,
        decoder_start_token_id=fast.pad_token_id,
        pad_token_id=fast.pad_token_id,
        eos_token_id=fast.eos_token_id,
        tie_word_embeddings=False)).eval()
    path.mkdir(parents=True, exist_ok=True)
    model.save_pretrained(path, safe_serialization=True)
    fast.save_pretrained(path)
    return path, model, fast


def build_chain_t5(path: Path, never: bool = False):
    """Programmed-chain T5 for the ENC-DEC scorer branch
    (compare_base_vs_instruct.py:188-237): all attention (self + cross) and
    FFN weights zeroed, one-hot shared embeddings, untied programmed
    lm_head. Cross-attention zero makes the decoder input-INDEPENDENT: the
    chain runs from decoder_start (pad), so every prompt produces the same
    designed completion — "w1 w2 Yes </s>" (top-2 find at position 2) or,
    with ``never=True``, a 3-word cycle whose top-2 never contains
    Yes/No inside the 10-position scan (the pos-0 fallback, :228-233).
    Returns (path, model, fast, (expected_position, expected_found))."""
    import torch
    import transformers as tf

    fast = _sp_tokenizer(with_pad=True)

    def pid(piece: str) -> int:
        # The backing tokenizer's token_to_id returns None for a missing
        # piece (the fast wrapper would silently fall back to <unk>).
        i = fast._tokenizer.token_to_id(piece)
        assert i is not None, f"piece {piece!r} not in vocab"
        return int(i)

    yes = pid("▁Yes")
    w = [pid("▁" + t) for t in ("a", "form", "of")]
    pad, eos = fast.pad_token_id, fast.eos_token_id
    if never:
        chain = {pad: (w[0], w[1]), w[0]: (w[1], w[2]), w[1]: (w[2], w[0]),
                 w[2]: (w[0], w[1])}
        expected = (0, False)
    else:
        chain = {pad: (w[0], w[1]), w[0]: (w[1], w[2]),
                 w[1]: (yes, w[2]), yes: (eos, w[0]), eos: (eos, w[0])}
        expected = (2, True)

    torch.manual_seed(4)
    model = tf.T5ForConditionalGeneration(tf.T5Config(
        vocab_size=len(fast), d_model=64, d_kv=16, d_ff=128,
        num_layers=1, num_decoder_layers=1, num_heads=4,
        decoder_start_token_id=pad, pad_token_id=pad, eos_token_id=eos,
        tie_word_embeddings=False)).eval()
    sd = model.state_dict()
    with torch.no_grad():
        for k, v in sd.items():
            if any(s in k for s in ("SelfAttention", "EncDecAttention",
                                    "DenseReluDense")):
                v.zero_()
            elif "layer_norm" in k or "final_layer_norm" in k:
                v.fill_(1.0)
        basis = {t: i for i, t in enumerate(chain)}
        junk = len(basis)
        assert junk < 64
        model.shared.weight.zero_()
        model.shared.weight[:, junk] = 4.0
        for t, b in basis.items():
            model.shared.weight[t, junk] = 0.0
            model.shared.weight[t, b] = 4.0
        model.lm_head.weight.zero_()           # (V, D)
        for t, (nxt, second) in chain.items():
            model.lm_head.weight[nxt, basis[t]] += 10.0
            model.lm_head.weight[second, basis[t]] += 5.0
        model.lm_head.weight[w[0], junk] += 10.0
        model.lm_head.weight[w[1], junk] += 5.0

    path.mkdir(parents=True, exist_ok=True)
    model.save_pretrained(path, safe_serialization=True)
    fast.save_pretrained(path)
    return path, model, fast, expected


# ---------------------------------------------------------------------------
# Programmed-chain GPT-2: argmax sequence is a designed function of the
# last prompt token, with +10/+5 margins so top-2 membership is exact on
# both torch and XLA.
# ---------------------------------------------------------------------------

# Chain prompts: each ends in a distinct anchor word whose LAST token seeds
# its chain. Kept single-word-ish so the BPE last token is stable.
CHAIN_PROMPTS = {
    # position 2: two preamble steps, then " Yes" as argmax
    "pos2_yes": 'Is a "screenshot" a "photograph"? photograph',
    # position 0: " No" immediately as argmax
    "pos0_no": 'Is a "drone" an "aircraft"? aircraft',
    # position 5: five preamble steps, then " Yes"
    "pos5_yes": 'Is a "tomato" a "vegetable"? vegetable',
    # runner-up: " No" enters top-2 at position 3 as the +5 SECOND token
    "runnerup_no": 'Is "humming" "singing"? singing',
    # never: 12-cycle of junk tokens, no Yes/No in any top-2 -> fallback
    "never": 'Is a "screenshot" a "quotation"? quotation',
}


def build_chain_gpt2(path: Path):
    """GPT-2 whose logits depend ONLY on the current token: zero attention
    and MLP outputs + zero positional embeddings leave h = ln_f(wte[t]);
    untied one-hot wte rows and a designed lm_head make
    logits[next(t)] ~ +10 and logits[second(t)] ~ +5. Returns
    (path, model, fast, expected) where expected maps CHAIN_PROMPTS keys to
    the designed (position_found, yes_no_found, argmax token text)."""
    import torch
    import transformers as tf

    # Reuse the trained BPE tokenizer so ids match the bpe-gpt2 family.
    fast = build_bpe_tokenizer()

    V = len(fast)
    D = 64

    def one(text: str) -> int:
        ids = fast(text, add_special_tokens=False).input_ids
        return ids[-1]

    yes_id = one(" Yes")
    no_id = one(" No")
    eos_id = fast.eos_token_id
    # Preamble/junk vocabulary (never Yes/No/eos):
    w = [one(t) for t in [" I", " think", " the", " answer", " is",
                          " clearly", " a", " b", " c", " d", " e", " f",
                          " g", " h"]]
    dot = one(".")
    anchors = [one(CHAIN_PROMPTS[k]) for k in CHAIN_PROMPTS]
    # Chain links use setdefault; any id collision would silently rewire a
    # designed position, so the whole cast must be distinct.
    cast = anchors + w + [dot, yes_id, no_id, eos_id]
    assert len(set(cast)) == len(cast), "chain token collision"

    chain: dict = {}          # token -> (argmax_next, second)

    def link(seq, second=None):
        for a, b in zip(seq, seq[1:]):
            chain.setdefault(a, (b, second or dot))

    # pos2_yes: anchor -> w0 -> w1 -> Yes -> . -> eos
    a1 = one(CHAIN_PROMPTS["pos2_yes"])
    link([a1, w[0], w[1], yes_id, dot, eos_id])
    # pos0_no: anchor -> No -> . -> eos
    a2 = one(CHAIN_PROMPTS["pos0_no"])
    link([a2, no_id])
    link([no_id, dot, eos_id])
    # pos5_yes: anchor -> w2..w6 -> Yes
    a3 = one(CHAIN_PROMPTS["pos5_yes"])
    link([a3, w[2], w[3], w[4], w[5], w[6], yes_id])
    # runnerup_no: anchor -> w7 -> w8 -> w9(second=No) -> w10 -> . -> eos;
    # at position 3 the argmax is w10 but the +5 runner-up is " No".
    a4 = one(CHAIN_PROMPTS["runnerup_no"])
    link([a4, w[7], w[8]])
    chain.setdefault(w[8], (w[9], dot))
    chain[w[9]] = (w[10], no_id)          # top-2 = {w10, No} here
    link([w[10], dot, eos_id])
    # never: anchor cycles junk for >10 steps
    a5 = one(CHAIN_PROMPTS["never"])
    link([a5, w[11], w[12], w[13]])
    chain[w[13]] = (w[11], dot)           # 3-cycle, never Yes/No
    chain.setdefault(yes_id, (dot, w[0]))
    chain.setdefault(dot, (eos_id, w[0]))
    chain[eos_id] = (eos_id, dot)         # eos self-loop: post-eos steps inert

    torch.manual_seed(3)
    cfg = tf.GPT2Config(vocab_size=V, n_embd=D, n_layer=1, n_head=1,
                        n_positions=256, tie_word_embeddings=False)
    model = tf.GPT2LMHeadModel(cfg).eval()
    sd = model.state_dict()
    with torch.no_grad():
        for k, v in sd.items():
            if any(s in k for s in ("attn", "mlp")) and k.endswith(
                    ("weight", "bias")):
                v.zero_()
        model.transformer.wpe.weight.zero_()
        # ln_1/ln_2 irrelevant (their block outputs are zeroed); ln_f = id-ish
        model.transformer.ln_f.weight.fill_(1.0)
        model.transformer.ln_f.bias.zero_()
        # One-hot-ish embeddings: chain tokens get unique basis vectors.
        model.transformer.wte.weight.zero_()
        basis = {}
        for t in chain:
            basis[t] = len(basis)
        assert len(basis) < D, "chain too large for hidden size"
        junk_axis = len(basis)            # shared axis for non-chain tokens
        for t in range(V):
            model.transformer.wte.weight[t, basis.get(t, junk_axis)] = 4.0
        # lm_head columns realize the transitions.
        model.lm_head.weight.zero_()
        for t, (nxt, second) in chain.items():
            model.lm_head.weight[nxt, basis[t]] += 10.0
            model.lm_head.weight[second, basis[t]] += 5.0
        # Non-chain tokens (every random prompt token) deterministically
        # enter the pos0_no chain so behavior is total.
        model.lm_head.weight[no_id, junk_axis] += 10.0
        model.lm_head.weight[dot, junk_axis] += 5.0

    path.mkdir(parents=True, exist_ok=True)
    model.save_pretrained(path, safe_serialization=True)
    fast.save_pretrained(path)
    expected = {
        "pos2_yes": (2, True),
        "pos0_no": (0, True),
        "pos5_yes": (5, True),
        "runnerup_no": (3, True),
        "never": (0, False),
    }
    return path, model, fast, expected
