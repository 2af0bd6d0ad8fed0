"""``references/sala.py`` and the cell ``minicpm-sala.sweep-doc16k`` (run by
hand, with the other tests of this directory): a document rows share passes
once and gives what every row alone gives; the controls read further off
than the reference's own float32; a whole run of the cell at a tiny size
answers ``correct`` true, and false with the selection broken underneath.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

import run as bench_run
import tiny
from references import sala as ref

SEED = 2**31 + 31


def _spec():
    raw = json.loads((bench_run.HERE / "configs" / "minicpm-sala.json"
                      ).read_text())
    return dataclasses.replace(
        ref.tiny(ref.spec_from_config("minicpm-sala", raw)), window=16,
        dense_len=32)


def _rows(rng, spec, documents=(3, 2), shared=64, width=96):
    rows = []
    for n in documents:
        doc = rng.integers(3, spec.vocab, shared)
        for _ in range(n):
            rows.append(np.concatenate(
                [doc, rng.integers(3, spec.vocab, width - shared)]))
    return np.stack(rows).astype(np.int32)


def test_a_shared_document_passes_once_and_changes_nothing(monkeypatch):
    spec = _spec()
    monkeypatch.setattr(ref, "SHARE_FROM", 40)
    tokens = _rows(np.random.default_rng(0), spec)
    tokens[1, 90:] = 0                                   # right padding
    positions = np.tile(np.arange(66, 96, 4)[None], (5, 1)).astype(np.int32)
    cluster, shared = ref.shared_documents(tokens, spec.block)
    assert cluster == [0, 0, 0, 1, 1] and shared == 64
    once = np.asarray(ref.logits_at(spec, SEED, tokens, positions))
    key = ref.seed_key(SEED)
    x = ref.forward_rows(spec, key, jax.numpy.asarray(tokens))
    alone = np.asarray(ref.unembed(
        spec, ref.top_made(spec, key), np.take_along_axis(
            np.asarray(x), positions[:, :, None], axis=1)))
    np.testing.assert_allclose(once, alone, atol=1e-5, rtol=0)
    # A position inside the shared part, or rows that share nothing, take
    # the row-by-row pass.
    early = positions.copy()
    early[0, 0] = 10
    assert np.isfinite(np.asarray(ref.logits_at(spec, SEED, tokens,
                                                early))).all()
    assert ref.shared_documents(tokens[[0, 3]], spec.block) == ([0, 1], 0)


@pytest.mark.parametrize("precision", ["int8", "fp8"])
def test_a_control_reads_further_off_than_float32(precision):
    spec = _spec()
    tokens = _rows(np.random.default_rng(1), spec, documents=(2,))
    positions = np.tile(np.arange(70, 96, 5)[None], (2, 1)).astype(np.int32)
    exact = np.asarray(ref.logits_at(spec, SEED, tokens, positions))
    low = np.asarray(ref.logits_at(spec, SEED, tokens, positions,
                                   precision=precision))
    assert 1e-3 < np.abs(low - exact).max() < 5.0


def _drive(monkeypatch, broken=False):
    from lir_tpu.models import decoder
    from lir_tpu.ops import sparse_attention

    monkeypatch.setattr(decoder, "CASCADE_INTERPRET_ON_CPU", True)
    if broken:
        # The programs the run before compiled are kept by shape for the
        # process: a patched selection has to be traced anew.
        from lir_tpu.engine import compile_plan

        compile_plan.exec_cache_clear()
        jax.clear_caches()
        real = sparse_attention.block_roles

        def all_blocks(qpos, main_len, n_blocks, **sizes):
            valid, _, bound = real(qpos, main_len, n_blocks, **sizes)
            return valid, valid, bound            # dense past dense_len

        monkeypatch.setattr(sparse_attention, "block_roles", all_blocks)
    cell, bench, files = tiny.files_for("minicpm-sala", "sweep-doc16k")
    # The instruments cut to 384 words, rephrasings of 392: the trunk is 48
    # blocks of 8, a window query keeps 1 + 24 + 2 of ~49. The window as the
    # cell has it: the anchor full, one group each other prompt.
    files["mix"] = dict(files["mix"], head_words=384, rephrasing_words=392,
                        group_rows=4, max_groups_per_prompt=2,
                        window_groups=6, reference_rows=4)
    files["runtime"] = dict(files["runtime"], batch_size=4, max_seq_len=768,
                            dispatch_tokens=1000)
    # The selection live reads ~0.04 / 0.0 at this size, skipped 0.3 / 0.36
    # and more, by what the window's length puts in the sample.
    files["limits"] = dict(files["limits"], logprob_gap=0.2, token_gap=0.2)
    monkeypatch.setattr(
        bench_run.sys.modules["harness.traffic"], "load_prompts",
        lambda mix, _real=bench_run.sys.modules[
            "harness.traffic"].load_prompts: [
            dataclasses.replace(p, main=" ".join(
                p.main.split()[:384] + p.main.split()[16000:]))
            for p in _real(mix)])
    return bench_run.drive(cell, bench, files, SEED + 7, 2.0, False,
                           jax.devices()[:1], check_config=False)


def test_a_whole_run_of_the_cell_at_a_tiny_size(monkeypatch):
    result = _drive(monkeypatch)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 5 + 6 * 4
    assert result["compared"]["logprob_gap"]["value"] < 0.2


def test_a_program_that_skips_the_selection_is_not_correct(monkeypatch):
    result = _drive(monkeypatch, broken=True)
    assert result["correct"] is False
