"""Sweep-driver tests: D1/D2/D6 row production, manifest resume, checkpoints.

Capability parity under test (SURVEY.md §2.1 C4/C5/C9/C11): grid expansion,
done-set dedup, checkpoint-every-N, append-with-schema-check — all with the
fake backend so no weights or network are needed.
"""

from pathlib import Path

import numpy as np
import pandas as pd
import pytest
import torch

from lir_tpu.backends.fake import FakeTokenizer
from lir_tpu.config import RuntimeConfig
from lir_tpu.data.prompts import LegalPrompt, WORD_MEANING_QUESTIONS, format_instruct_prompt
from lir_tpu.engine import grid as grid_mod
from lir_tpu.engine.runner import ScoringEngine
from lir_tpu.engine.sweep import run_perturbation_sweep, run_word_meaning_sweep
from lir_tpu.models.loader import config_from_hf, convert_decoder
from lir_tpu.utils.manifest import SweepManifest


def _engine(batch_size=4, max_new=8):
    import transformers as tf
    torch.manual_seed(0)
    hf = tf.LlamaForCausalLM(tf.LlamaConfig(
        vocab_size=FakeTokenizer.VOCAB, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=4, intermediate_size=128,
        max_position_embeddings=512, tie_word_embeddings=False)).eval()
    cfg, fam = config_from_hf(hf.config)
    params = convert_decoder(hf.state_dict(), cfg, fam)
    return ScoringEngine(params, cfg, FakeTokenizer(),
                         RuntimeConfig(batch_size=batch_size,
                                       max_new_tokens=max_new,
                                       max_seq_len=256))


PROMPTS = (
    LegalPrompt(
        main="Does a vehicle include a bicycle ?",
        response_format="Answer Covered or Not .",
        target_tokens=("Covered", "Not"),
        confidence_format="Give a number from 0 to 100 .",
    ),
    LegalPrompt(
        main="Is a drone an aircraft ?",
        response_format="Answer Yes or No .",
        target_tokens=("Yes", "No"),
        confidence_format="Give a number from 0 to 100 .",
    ),
)
PERTURBATIONS = (
    ["Would a bicycle count as a vehicle ?", "Can a bicycle be a vehicle ?"],
    ["Would a drone count as an aircraft ?"],
)


def test_grid_expansion_and_subset():
    cells = grid_mod.build_grid("m", PROMPTS, PERTURBATIONS)
    # original + rephrasings per prompt: (1+2) + (1+1) = 5
    assert len(cells) == 5
    assert cells[0].rephrase_idx == 0
    assert cells[0].rephrased_main == PROMPTS[0].main
    sub = grid_mod.random_subset(cells, 3, seed=42)
    assert len(sub) == 3
    assert grid_mod.random_subset(cells, 3, seed=42) == sub  # deterministic


@pytest.mark.slow
def test_perturbation_sweep_writes_d6_and_resumes(tmp_path):
    eng = _engine()
    out = tmp_path / "results.xlsx"
    rows = run_perturbation_sweep(eng, "tiny-llama", PROMPTS, PERTURBATIONS,
                                  out, checkpoint_every=2)
    assert len(rows) == 5
    from lir_tpu.data.schemas import read_results_frame
    df = read_results_frame(out)
    assert len(df) == 5
    from lir_tpu.data.schemas import PERTURBATION_COLUMNS
    assert list(df.columns) == list(PERTURBATION_COLUMNS)
    assert df["Token_1_Prob"].between(0, 1).all()
    assert df["Weighted Confidence"].between(0, 100).all()
    # Log Probabilities column holds a parseable top-20 map.
    import json
    lp = json.loads(df["Log Probabilities"].iloc[0])
    assert len(lp) == 20

    # Resume: everything already done -> no new rows, file unchanged.
    rows2 = run_perturbation_sweep(eng, "tiny-llama", PROMPTS, PERTURBATIONS,
                                   out, checkpoint_every=2)
    assert rows2 == []
    assert len(read_results_frame(out)) == 5

    # A new model re-runs the full grid (key includes model).
    rows3 = run_perturbation_sweep(eng, "tiny-llama-2", PROMPTS, PERTURBATIONS,
                                   out, checkpoint_every=2)
    assert len(rows3) == 5
    assert len(read_results_frame(out)) == 10


@pytest.mark.slow
def test_word_meaning_sweep_rows():
    eng = _engine(batch_size=8)
    questions = list(WORD_MEANING_QUESTIONS[:6])
    rows = run_word_meaning_sweep(eng, "tiny-llama", "instruct", questions,
                                  format_instruct_prompt)
    assert len(rows) == 6
    for q, r in zip(questions, rows):
        assert r.prompt == q
        assert r.model == "tiny-llama"
        assert 0 <= r.yes_prob <= 1 and 0 <= r.no_prob <= 1


@pytest.mark.slow
def test_reasoning_count_averaging_matches_api_decoder():
    """VERDICT r1 #7: the local n-run averaging must binarize with the same
    if/elif order as the API decoder (perturb_prompts.py:423-426) — a text
    containing BOTH targets ("Not Covered" contains "Covered") counts toward
    token 1 only."""
    from lir_tpu.backends import api
    from lir_tpu.engine.grid import GridCell

    runs = ["Not Covered", "Covered", "Covered", "no idea", "Not"]
    targets = ("Covered", "Not")

    # API side: feed the same run texts through _finalize_reasoning.
    cell = GridCell(prompt_idx=0, rephrase_idx=0, model="m",
                    original_main="o", rephrased_main="r",
                    response_format="f", confidence_format="c",
                    target_tokens=targets)
    score = api.ApiScore(custom_id="p0_r0")
    score.run_responses = list(runs)
    scores = {"p0_r0": score}
    api._finalize_reasoning(scores, {"p0_r0_binary_run0": cell})

    # Local side: scripted sampler returning one run text per call.
    engine = _engine(batch_size=2, max_new=4)
    it = iter(runs)

    def scripted(toks, mask, key, temperature, max_new_tokens):
        return [next(it)] * int(toks.shape[0])

    engine._sample_from_ids = scripted
    res = engine.score_prompts_sampled(
        ["b"], [targets], n_runs=len(runs))[0]

    assert res.token_1_prob == score.token_1_prob == 3 / 5
    assert res.token_2_prob == score.token_2_prob == 1 / 5
    assert res.odds_ratio == score.token_1_prob / score.token_2_prob
    assert res.response == "Covered"  # most common (2x exact)


@pytest.mark.slow
def test_reasoning_sweep_writes_count_fraction_rows(tmp_path):
    """End-to-end reasoning mode on the tiny model: D6 rows carry count
    fractions (multiples of 1/n_runs) and Weighted Confidence equals the
    parsed integer (perturb_prompts.py:459-464)."""
    engine = _engine(batch_size=4, max_new=4)
    out = tmp_path / "results.csv"
    rows = run_perturbation_sweep(
        engine, "tiny-reasoner", PROMPTS, PERTURBATIONS, out,
        reasoning=True, reasoning_runs=4)
    # grid = original + rephrasings per prompt: (1+2) + (1+1) = 5 cells
    assert len(rows) == 5
    for r in rows:
        for p in (r.token_1_prob, r.token_2_prob):
            assert abs(p * 4 - round(p * 4)) < 1e-9
        assert r.log_probabilities == ""
        if r.confidence_value is None:
            assert r.weighted_confidence is None
        else:
            assert r.weighted_confidence == float(r.confidence_value)
    df = pd.read_csv(out)
    assert len(df) == 5


@pytest.mark.slow
def test_reasoning_resume_is_cell_deterministic(tmp_path):
    """PRNG streams are keyed by grid-cell identity, so a resumed sweep
    (different todo/batch composition) samples exactly what the
    uninterrupted run sampled for every remaining cell."""
    engine = _engine(batch_size=4, max_new=4)
    full_rows = run_perturbation_sweep(
        engine, "m", PROMPTS, PERTURBATIONS, tmp_path / "full.csv",
        reasoning=True, reasoning_runs=3)
    by_cell = {(r.original_main, r.rephrased_main): r for r in full_rows}

    # Pre-mark the first three cells done; the "resumed" run scores only the
    # remaining two, in a smaller tail bucket.
    manifest = SweepManifest(tmp_path / "resumed.manifest.jsonl",
                             grid_mod.RESUME_KEY_FIELDS)
    manifest.mark_done_many([
        {"model": "m", "original_main": r.original_main,
         "rephrased_main": r.rephrased_main} for r in full_rows[:3]])
    resumed = run_perturbation_sweep(
        engine, "m", PROMPTS, PERTURBATIONS, tmp_path / "resumed.csv",
        manifest=manifest, reasoning=True, reasoning_runs=3)
    assert len(resumed) == 2
    for r in resumed:
        ref = by_cell[(r.original_main, r.rephrased_main)]
        assert r.token_1_prob == ref.token_1_prob
        assert r.token_2_prob == ref.token_2_prob
        assert r.model_response == ref.model_response
        assert r.model_confidence_response == ref.model_confidence_response


def test_parse_confidence_truncation_guard():
    """A budget-limited decode that never reached EOS must not trust an
    integer whose digits touch the end of the text (possibly cut mid-number:
    '...about 85' truncated to '...about 8')."""
    from lir_tpu.engine.sweep import _parse_confidence

    assert _parse_confidence("I am about 85% sure", complete=False) == 85
    assert _parse_confidence("confidence: 85", complete=True) == 85
    assert _parse_confidence("confidence: 8", complete=False) is None
    assert _parse_confidence("confidence: 85 .", complete=False) == 85
    assert _parse_confidence("no number here", complete=False) is None


@pytest.mark.slow
def test_perturbation_sweep_multihost_shards(tmp_path, monkeypatch):
    """Under a (simulated) 2-process pod, each host sweeps HALF the grid
    into its own .hostN results + manifest (disjoint writes), and the two
    shards partition the cells exactly."""
    import jax

    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.config import RuntimeConfig
    from lir_tpu.data.prompts import LegalPrompt
    from lir_tpu.engine.runner import ScoringEngine
    from lir_tpu.engine.sweep import run_perturbation_sweep
    from lir_tpu.models import decoder
    from lir_tpu.models.registry import ModelConfig
    from lir_tpu.parallel import multihost

    cfg = ModelConfig(name="mh", vocab_size=FakeTokenizer.VOCAB,
                      hidden_size=32, n_layers=2, n_heads=4,
                      intermediate_size=64, max_seq_len=128)
    eng = ScoringEngine(decoder.init_params(cfg, jax.random.PRNGKey(0)),
                        cfg, FakeTokenizer(),
                        RuntimeConfig(batch_size=4, max_new_tokens=4))
    lp = (LegalPrompt(main="Is a levee failure a flood ?",
                      response_format="Answer Yes or No .",
                      target_tokens=("Yes", "No"),
                      confidence_format="Number 0 to 100 ."),)
    perts = ([f"variant {i} of the levee question ?" for i in range(5)],)

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    # A real barrier would block: this simulation has one actual process.
    monkeypatch.setattr(multihost, "barrier", lambda name: None)
    monkeypatch.setattr(multihost, "liveness_barrier",
                        lambda name, **kw: None)
    seen = []
    for proc in (0, 1):
        monkeypatch.setattr(jax, "process_index", lambda p=proc: p)
        assert multihost.is_multiprocess()
        rows = run_perturbation_sweep(
            eng, "mh-model", lp, perts, tmp_path / "results.xlsx",
            checkpoint_every=3)
        out = tmp_path / f"results.host{proc}.csv"
        assert out.exists(), list(tmp_path.iterdir())
        assert (tmp_path / f"results.host{proc}.manifest.jsonl").exists()
        seen.extend((r.original_main, r.rephrased_main) for r in rows)
    # 6 cells total (original + 5 rephrasings), split 3/3, no overlap.
    assert len(seen) == 6 and len(set(seen)) == 6


def test_multihost_required_single_process_runtime_error_attribution(
        monkeypatch):
    """A launcher that pre-initialized jax.distributed with a SINGLE-process
    topology must get an error naming that state — not a misattributed
    'bring-up failed' (ADVICE r3 #3)."""
    import jax
    import pytest

    from lir_tpu.parallel import multihost

    def boom(*a, **k):
        raise RuntimeError("distributed runtime already initialized")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    monkeypatch.setattr(jax, "process_count", lambda: 1)
    # raising=False: older jax has no is_initialized at all (multihost
    # probes it defensively), so the patch must not require the attribute.
    monkeypatch.setattr(jax.distributed, "is_initialized", lambda: True,
                        raising=False)
    with pytest.raises(RuntimeError, match="SINGLE-process topology"):
        multihost.initialize(required=True)
    # With no runtime at all, the plain bring-up-failed error stands.
    monkeypatch.setattr(jax.distributed, "is_initialized", lambda: False,
                        raising=False)
    with pytest.raises(RuntimeError, match="bring-up failed"):
        multihost.initialize(required=True)
    # initialize() "succeeding" but finding no peers is the same hazard.
    monkeypatch.setattr(jax.distributed, "initialize", lambda *a, **k: None)
    with pytest.raises(RuntimeError, match="no peers were found"):
        multihost.initialize(required=True)


@pytest.mark.slow
def test_multihost_shard_concat_and_merged_resume(tmp_path, monkeypatch):
    """The gather step: after both hosts sweep their shards, host 0 merges
    the .hostN workbooks + manifests into the FINAL artifact
    (perturb_prompts.py:161-188,975-984 semantics), and a later
    single-process resume against the merged manifest scores nothing."""
    import jax

    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.config import RuntimeConfig
    from lir_tpu.data import schemas
    from lir_tpu.data.prompts import LegalPrompt
    from lir_tpu.engine import grid as grid_mod
    from lir_tpu.engine.runner import ScoringEngine
    from lir_tpu.engine.sweep import run_perturbation_sweep
    from lir_tpu.models import decoder
    from lir_tpu.models.registry import ModelConfig
    from lir_tpu.parallel import multihost
    from lir_tpu.utils.manifest import SweepManifest

    cfg = ModelConfig(name="mhc", vocab_size=FakeTokenizer.VOCAB,
                      hidden_size=32, n_layers=2, n_heads=4,
                      intermediate_size=64, max_seq_len=128)
    eng = ScoringEngine(decoder.init_params(cfg, jax.random.PRNGKey(0)),
                        cfg, FakeTokenizer(),
                        RuntimeConfig(batch_size=4, max_new_tokens=4))
    lp = (LegalPrompt(main="Is a levee failure a flood ?",
                      response_format="Answer Yes or No .",
                      target_tokens=("Yes", "No"),
                      confidence_format="Number 0 to 100 ."),)
    perts = ([f"variant {i} of the levee question ?" for i in range(5)],)

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(multihost, "barrier", lambda name: None)
    monkeypatch.setattr(multihost, "liveness_barrier",
                        lambda name, **kw: None)
    # Host 1 first, then host 0 (whose tail runs the merge).
    for proc in (1, 0):
        monkeypatch.setattr(jax, "process_index", lambda p=proc: p)
        run_perturbation_sweep(eng, "mhc-model", lp, perts,
                               tmp_path / "results.xlsx", checkpoint_every=3)

    final = schemas.resolve_results_path(tmp_path / "results.xlsx")
    assert final.exists()
    df = schemas.read_results_frame(final)
    assert len(df) == 6
    assert list(df.columns) == list(schemas.PERTURBATION_COLUMNS)
    assert len(set(df["Rephrased Main Part"])) == 6
    # Per-host shards/manifests survive (per-host resume keeps working).
    assert (tmp_path / "results.host0.csv").exists()
    assert (tmp_path / "results.host1.manifest.jsonl").exists()
    # Merged manifest covers ALL cells: a single-process resume runs dry.
    merged_manifest = SweepManifest(final.with_suffix(".manifest.jsonl"),
                                    grid_mod.RESUME_KEY_FIELDS)
    cells = grid_mod.build_grid("mhc-model", lp, perts)
    assert grid_mod.pending_cells(cells, merged_manifest) == []


@pytest.mark.slow
def test_multihost_empty_host_still_merges(tmp_path, monkeypatch):
    """A pod larger than the grid: hosts with zero assigned cells write a
    header-only shard, so host 0's merge still produces the final artifact
    instead of mistaking the empty host for a missing filesystem."""
    import jax

    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.config import RuntimeConfig
    from lir_tpu.data import schemas
    from lir_tpu.data.prompts import LegalPrompt
    from lir_tpu.engine.runner import ScoringEngine
    from lir_tpu.engine.sweep import run_perturbation_sweep
    from lir_tpu.models import decoder
    from lir_tpu.models.registry import ModelConfig
    from lir_tpu.parallel import multihost

    cfg = ModelConfig(name="mhe", vocab_size=FakeTokenizer.VOCAB,
                      hidden_size=32, n_layers=2, n_heads=4,
                      intermediate_size=64, max_seq_len=128)
    eng = ScoringEngine(decoder.init_params(cfg, jax.random.PRNGKey(0)),
                        cfg, FakeTokenizer(),
                        RuntimeConfig(batch_size=4, max_new_tokens=4))
    lp = (LegalPrompt(main="Is a levee failure a flood ?",
                      response_format="Answer Yes or No .",
                      target_tokens=("Yes", "No"),
                      confidence_format="Number 0 to 100 ."),)
    # 2 cells total on a 3-host pod: host 2 gets nothing.
    perts = (["variant zero of the levee question ?"],)

    monkeypatch.setattr(jax, "process_count", lambda: 3)
    monkeypatch.setattr(multihost, "barrier", lambda name: None)
    monkeypatch.setattr(multihost, "liveness_barrier",
                        lambda name, **kw: None)
    for proc in (2, 1, 0):
        monkeypatch.setattr(jax, "process_index", lambda p=proc: p)
        run_perturbation_sweep(eng, "mhe-model", lp, perts,
                               tmp_path / "results.xlsx", checkpoint_every=3)

    assert (tmp_path / "results.host2.csv").exists()   # header-only shard
    final = schemas.resolve_results_path(tmp_path / "results.xlsx")
    df = schemas.read_results_frame(final)
    assert len(df) == 2
    assert list(df.columns) == list(schemas.PERTURBATION_COLUMNS)


def test_cli_concat_shards(tmp_path, capsys):
    """`lir_tpu concat-shards` merges .hostN shards from the command line
    (the manual gather for pods without a shared filesystem)."""
    from lir_tpu import cli
    from lir_tpu.data import schemas
    from lir_tpu.data.schemas import PerturbationRow

    def rows(tag):
        return [PerturbationRow(
            model="m", original_main="q", response_format="rf",
            confidence_format="cf", rephrased_main=f"{tag}-{i}",
            full_rephrased_prompt="p", full_confidence_prompt="c",
            model_response="Yes", model_confidence_response="85",
            log_probabilities="{}", token_1_prob=0.6, token_2_prob=0.3,
            confidence_value=85, weighted_confidence=80.0) for i in range(2)]

    for h in (0, 1):
        schemas.write_perturbation_results(
            rows(f"h{h}"), tmp_path / f"results.host{h}.csv")
        (tmp_path / f"results.host{h}.manifest.jsonl").write_text(
            "\n".join('{"model": "m", "original_main": "q", '
                      f'"rephrased_main": "h{h}-{i}"}}' for i in range(2))
            + "\n")
    cli.main(["concat-shards", "--results", str(tmp_path / "results.csv"),
              "--hosts", "2"])
    assert "merged 4 rows" in capsys.readouterr().out
    df = schemas.read_results_frame(tmp_path / "results.csv")
    assert len(df) == 4

    with pytest.raises(SystemExit, match="no mergeable shards"):
        cli.main(["concat-shards", "--results",
                  str(tmp_path / "missing.csv"), "--hosts", "2"])


def test_cli_concat_shards_xlsx_request_finds_csv_shards(tmp_path, capsys):
    """Pod hosts without openpyxl write .csv shards; an operator following
    DEPLOY.md with --results results.xlsx must still find them, and a
    merge without shard manifests warns instead of claiming one."""
    from lir_tpu import cli
    from lir_tpu.data import schemas
    from lir_tpu.data.schemas import PerturbationRow

    row = PerturbationRow(
        model="m", original_main="q", response_format="rf",
        confidence_format="cf", rephrased_main="r",
        full_rephrased_prompt="p", full_confidence_prompt="c",
        model_response="Yes", model_confidence_response="85",
        log_probabilities="{}", token_1_prob=0.6, token_2_prob=0.3,
        confidence_value=85, weighted_confidence=80.0)
    for h in (0, 1):
        schemas.write_perturbation_results(
            [row], tmp_path / f"results.host{h}.csv")
    cli.main(["concat-shards", "--results", str(tmp_path / "results.xlsx"),
              "--hosts", "2"])
    out = capsys.readouterr().out
    assert "merged 2 rows" in out
    assert "WARNING: no shard manifests" in out


@pytest.mark.slow
def test_pipelined_writer_failure_preserves_resume(tmp_path, monkeypatch):
    """A flush failure inside the writer thread must re-raise on the
    caller's thread, and the write-ahead guarantee must hold: only rows
    from SUCCESSFUL flushes are marked done, so a resumed sweep re-scores
    exactly the unflushed cells and the final artifact is complete."""
    import jax

    from lir_tpu.backends.fake import FakeTokenizer
    from lir_tpu.config import RuntimeConfig
    from lir_tpu.data import schemas
    from lir_tpu.engine import sweep as sweep_mod
    from lir_tpu.engine.runner import ScoringEngine
    from lir_tpu.models import decoder
    from lir_tpu.models.registry import ModelConfig

    cfg = ModelConfig(name="wf", vocab_size=FakeTokenizer.VOCAB,
                      hidden_size=32, n_layers=2, n_heads=4,
                      intermediate_size=64, max_seq_len=128)
    eng = ScoringEngine(decoder.init_params(cfg, jax.random.PRNGKey(0)),
                        cfg, FakeTokenizer(),
                        RuntimeConfig(batch_size=2, max_new_tokens=4))
    lp = (LegalPrompt(main="Is a levee failure a flood ?",
                      response_format="Answer Yes or No .",
                      target_tokens=("Yes", "No"),
                      confidence_format="Number 0 to 100 ."),)
    perts = ([f"variant {i} ?" for i in range(5)],)  # 6 cells, batches of 2

    real_write = schemas.write_perturbation_results
    calls = {"n": 0}

    def failing_write(rows, path, append=True):
        calls["n"] += 1
        if calls["n"] == 2:          # second flush dies (disk full, etc.)
            raise OSError("disk full")
        return real_write(rows, path, append=append)

    monkeypatch.setattr(sweep_mod.schemas, "write_perturbation_results",
                        failing_write)
    out = tmp_path / "results.csv"
    with pytest.raises(OSError, match="disk full"):
        run_perturbation_sweep(eng, "wf-model", lp, perts, out,
                               checkpoint_every=2)
    # First flush landed; its rows (and ONLY its rows) are marked done.
    manifest_lines = [
        l for l in (out.with_suffix(".manifest.jsonl")
                    .read_text().splitlines()) if l]
    assert len(manifest_lines) == 2
    assert len(schemas.read_results_frame(out)) == 2

    monkeypatch.setattr(sweep_mod.schemas, "write_perturbation_results",
                        real_write)
    resumed = run_perturbation_sweep(eng, "wf-model", lp, perts, out,
                                     checkpoint_every=2)
    assert len(resumed) == 4         # exactly the unflushed cells
    df = schemas.read_results_frame(out)
    assert len(df) == 6
    assert len(set(df["Rephrased Main Part"])) == 6


# ---------------------------------------------------------------------------
# Plan windows (ISSUE 35): a long-document call is filled, planned and
# dispatched a prompt at a time, the rest of the fill behind the device
# ---------------------------------------------------------------------------

_WORDS = ("coverage policy flood water damage claim insurer premium "
          "exclusion endorsement peril deductible adjuster settle").split()


def _doc_traffic(groups=(0, 2, 1, 0, 1), docs=None, seed=3, wordy=3):
    """doc16k-shaped traffic at a tiny size: five prompts, each a
    160-word instrument of its own followed by its question; rephrasings
    keep the instrument verbatim, in groups of 4 a prompt (``groups``:
    on three prompts, so two originals are alone). Prompt ``wordy``, a
    lone original, has the longest answer format of all. ``docs``: which
    instrument each prompt stands on."""
    rng = np.random.default_rng(seed)
    docs = list(range(len(groups))) if docs is None else docs
    texts = {d: " ".join(rng.choice(_WORDS) for _ in range(160))
             for d in set(docs)}

    def main(p):
        return (texts[docs[p]] + " "
                + " ".join(rng.choice(_WORDS) for _ in range(8)) + " ?")

    formats = ["Answer Yes or No ."] * len(groups)
    formats[wordy] = ("Answer Yes if the instrument covers it and No if "
                      "it does not .")
    prompts = tuple(LegalPrompt(
        main=main(p), response_format=formats[p], target_tokens=("Yes", "No"),
        confidence_format="Give a number from 0 to 100 .")
        for p in range(len(groups)))
    perts = tuple([main(p) for _ in range(4 * g)]
                  for p, g in enumerate(groups))
    return prompts, perts


def _short_traffic(seed=4):
    """trunk512-shaped traffic at a tiny size: rephrasings of ~40 words
    that keep their prompt's first 16."""
    rng = np.random.default_rng(seed)

    def words(n):
        return " ".join(rng.choice(_WORDS) for _ in range(n))

    heads = [words(16) for _ in range(3)]
    prompts = tuple(LegalPrompt(
        main=h + " " + words(20) + " ?", response_format="Answer Yes or No .",
        target_tokens=("Yes", "No"),
        confidence_format="Give a number from 0 to 100 .") for h in heads)
    perts = tuple([h + " " + words(24 + i % 3) + " ?" for i in range(n)]
                  for h, n in zip(heads, (8, 4, 4)))
    return prompts, perts


class _LoggingTokenizer(FakeTokenizer):
    """Logs every text it is given; ``poison`` makes one text fail."""

    def __init__(self):
        super().__init__()
        self.log = []
        self.poison = None

    def __call__(self, text, add_special_tokens=True):
        if text == self.poison:
            raise RuntimeError("tokenizer: poisoned text")
        self.log.append(text)
        return super().__call__(text, add_special_tokens)


def _window_engine(shape):
    """A tiny model of layer kinds under a token cap (``doc``: 160-token
    instruments in the 256 bucket, cap 384, batch 4), or a tiny dense
    one with no cap (``short``)."""
    import dataclasses

    import jax

    from lir_tpu.models import decoder
    from lir_tpu.models.registry import ModelConfig

    tokenizer = _LoggingTokenizer()
    if shape == "short":
        cfg = ModelConfig(name="win-short", vocab_size=FakeTokenizer.VOCAB,
                          hidden_size=32, n_layers=2, n_heads=4,
                          intermediate_size=64, max_seq_len=128)
        return ScoringEngine(
            decoder.init_params(cfg, jax.random.PRNGKey(0)), cfg, tokenizer,
            RuntimeConfig(batch_size=4, max_new_tokens=4, max_seq_len=128))
    import test_sala_model as sa

    spec = dataclasses.replace(sa._tiny("lightning-first"), window=96,
                               vocab=FakeTokenizer.VOCAB)
    cfg, params = sa._model(spec)
    return ScoringEngine(params, cfg, tokenizer,
                         RuntimeConfig(batch_size=4, max_seq_len=256,
                                       sweep_group_min_cells=0,
                                       dispatch_tokens=384))


def _swept(engine, traffic, path, whole_grid=False, **kw):
    """One run_perturbation_sweep call, looked at from outside: the plan
    windows it made, the tokenizer's log with a ``None`` where each
    device dispatch was issued, what the counters gained. ``whole_grid``
    plans the parent's way: no window ever closes."""
    from lir_tpu.engine import scheduler as sched_mod
    from lir_tpu.engine import sweep as sweep_mod
    from lir_tpu.models import decoder

    windows, log = [], engine.tokenizer.log
    del log[:]
    real_fill, real_shared = sweep_mod._fill_windows, engine.decode_fused_shared

    def recording(*a, **k):
        for w in real_fill(*a, **k):
            windows.append(w)
            yield w

    def marking(*a, **k):
        log.append(None)
        return real_shared(*a, **k)

    fill, casc = engine.fill_stats, engine.cascade_stats
    before = (fill.fill_s, fill.ahead_s, fill.windows, fill.windows_ahead,
              casc.trunk_programs, engine.compile_stats.lazy_misses)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(decoder, "CASCADE_INTERPRET_ON_CPU", True)
        mp.setattr(sweep_mod, "_fill_windows", recording)
        mp.setattr(engine, "decode_fused_shared", marking)
        if whole_grid:
            mp.setattr(sched_mod.RaggedScheduler, "closed",
                       lambda self, items: False)
        try:
            rows = run_perturbation_sweep(engine, "m", *traffic, path, **kw)
            error = None
        except (OSError, RuntimeError) as err:
            rows, error = None, err
    engine.exec_registry.wait()
    after = (fill.fill_s, fill.ahead_s, fill.windows, fill.windows_ahead,
             casc.trunk_programs, engine.compile_stats.lazy_misses)
    gained = dict(zip(("fill_s", "ahead_s", "windows", "windows_ahead",
                       "trunk_programs", "lazy_misses"),
                      (a - b for a, b in zip(after, before))))
    return dict(rows=rows, error=error, windows=list(windows),
                log=list(log), gained=gained,
                shapes=set(engine.exec_registry._futures))


def _dispatch_list(run):
    """Cells, order, edges, suffix buckets and route of every dispatch,
    and the trunk each leaves held."""
    return [([(c.prompt_idx, c.rephrase_idx) for c in d.cells], d.kind,
             d.bucket, d.edge, d.sfx_bucket_a, d.sfx_bucket_b, r, r.held_ids)
            for w in run["windows"]
            for d, r in zip(w.dispatches, w.routes)]


def _row_values(rows):
    return [(r.original_main, r.rephrased_main, r.model_response,
             r.model_confidence_response, r.log_probabilities,
             r.token_1_prob, r.token_2_prob, r.confidence_value,
             r.weighted_confidence) for r in rows]


@pytest.fixture(scope="module")
def window_runs(tmp_path_factory):
    """The doc16k-shaped call planned in windows and planned whole, on
    one engine (the second call finds the first one's programs), and the
    trunk512-shaped call."""
    out = tmp_path_factory.mktemp("windows")
    doc, short = _window_engine("doc"), _window_engine("short")
    traffic = _doc_traffic()
    return {
        "doc": _swept(doc, traffic, out / "doc.csv"),
        "doc-whole": _swept(doc, traffic, out / "doc-whole.csv",
                            whole_grid=True),
        "doc-warm": _swept(doc, traffic, out / "doc-warm.csv"),
        "short": _swept(short, _short_traffic(), out / "short.csv"),
        "doc-engine": doc, "short-engine": short,
        "traffic": {"doc": traffic, "short": _short_traffic()},
    }


def test_a_long_document_call_runs_the_whole_grids_plan_in_windows(
        window_runs):
    """(a) Five closed windows; their dispatches are the whole-grid
    plan's to the last field (cells, order, edges, suffix buckets,
    routes, the trunks held), the same programs are planned, the same
    trunk programs run, and the rows are bitwise equal."""
    run, whole = window_runs["doc"], window_runs["doc-whole"]
    assert [w.more for w in run["windows"]] == [True] * 4 + [False]
    assert len(whole["windows"]) == 1
    assert _dispatch_list(run) == _dispatch_list(whole)
    assert [len(d[0]) for d in _dispatch_list(run)] == [
        1, 4, 4, 1, 4, 1, 1, 4, 1]
    # The longest format is a LONE original's, three windows on: the
    # first window already runs at its suffix edge.
    assert {(d[3], d[4], d[5]) for d in _dispatch_list(run)} == {
        (192, 16, 8)}
    assert run["shapes"] == whole["shapes"]
    assert run["gained"]["trunk_programs"] == 3 == whole["gained"][
        "trunk_programs"]
    assert run["gained"]["lazy_misses"] == 0
    assert len(run["rows"]) == 21
    assert _row_values(run["rows"]) == _row_values(whole["rows"])


def test_a_short_row_call_is_one_window_and_the_parents_plan(window_runs):
    """(b) No cap: ONE window, whose dispatches are what the scheduler
    plans for the whole tokenized grid."""
    from lir_tpu.engine import scheduler as sched_mod
    from lir_tpu.engine import sweep as sweep_mod

    run, engine = window_runs["short"], window_runs["short-engine"]
    assert [w.more for w in run["windows"]] == [False]
    prompts, perts = window_runs["traffic"]["short"]
    cells = grid_mod.build_grid("m", prompts, perts)
    items = sched_mod.build_items(
        [engine.tokenizer(c.binary_prompt).input_ids for c in cells],
        [engine.tokenizer(c.confidence_prompt).input_ids for c in cells],
        cells)
    new = min(engine.rt.sweep_decode_tokens, engine.rt.max_new_tokens)
    conf = min(engine.rt.sweep_confidence_tokens, engine.rt.max_new_tokens)
    want = sweep_mod._ragged_planner(engine, new, conf).schedule(items)
    got = run["windows"][0].dispatches
    assert [(d.kind, d.bucket, d.edge, d.sfx_bucket_a, d.sfx_bucket_b,
             d.refilled, d.cells) for d in got] == [
        (d.kind, d.bucket, d.edge, d.sfx_bucket_a, d.sfx_bucket_b,
         d.refilled, d.cells) for d in want]
    assert len(run["rows"]) == len(cells) == 19


@pytest.mark.parametrize("shape", ["doc", "doc-warm", "short"])
def test_the_device_starts_after_the_first_window_is_tokenized(
        window_runs, shape):
    """(c) With a cap the first dispatch is issued when the first
    prompt's cells and ONE cell of every other prompt have been
    tokenized, before any other cell of the last prompt; without one,
    after the whole grid. Either way every text is tokenized once. In
    a call whose programs still compile (``doc``: the engine's first)
    the fill starts at once, beside the first program's load, and is by
    then its two windows ahead and no more."""
    run = window_runs[shape]
    prompts, perts = window_runs["traffic"][shape.split("-")[0]]
    cells = grid_mod.build_grid("m", prompts, perts)
    def texts(cs):
        return {t for c in cs
                for t in (c.binary_prompt, c.confidence_prompt)}

    # The prompts' texts alone (the engine also asks for target words
    # and digits), and a None where a dispatch was issued.
    log = [t for t in run["log"] if t is None or t in texts(cells)]
    before = set(log[:log.index(None)])
    said = [t for t in log if t is not None]
    assert len(said) == len(set(said)) == 2 * len(cells)
    if shape == "short":
        assert before == texts(cells)
        return
    first = [c for c in cells if c.prompt_idx == 0]
    heads = [c for c in cells if c.rephrase_idx == 0]
    ahead = [c for c in cells if c.prompt_idx in (1, 2)]
    if shape == "doc":
        assert texts(first + heads) <= before <= texts(first + heads + ahead)
    else:
        assert before == texts(first + heads)
    last = [c for c in cells if c.prompt_idx == 4 and c.rephrase_idx]
    assert last and not texts(last) & before
    # ... and the fill never runs more than two windows ahead of the
    # dispatch loop: when the second window's first dispatch is issued,
    # the last prompt's rephrasings are still not tokenized.
    second = [i for i, t in enumerate(log) if t is None][1]
    assert not texts(last) & set(log[:second])


def test_a_held_trunk_survives_a_window_boundary(window_runs, tmp_path):
    """(d) Two questions on ONE instrument are two windows; the second
    is routed behind the trunk the first left held, so the trunk program
    runs once for both (as in the whole-grid plan), no program is
    compiled that the plan did not hold, and nothing is held from the
    call before (fresh_handoff once a call, not once a window)."""
    engine = window_runs["doc-engine"]
    traffic = _doc_traffic(groups=(1, 1, 0), docs=[0, 0, 1], seed=5,
                           wordy=2)
    traffic = (traffic[0], (traffic[1][0][:3], traffic[1][1][:3], []))
    run = _swept(engine, traffic, tmp_path / "same.csv")
    whole = _swept(engine, traffic, tmp_path / "same-whole.csv",
                   whole_grid=True)
    assert [len(w.dispatches) for w in run["windows"]] == [1, 1, 1]
    assert _dispatch_list(run) == _dispatch_list(whole)
    first, second, lone = (w.routes[0] for w in run["windows"])
    assert (first.held, first.trunk_run) == (True, True)
    assert (second.held, second.trunk_run) == (True, False)
    assert second.held_ids == first.held_ids == lone.held_ids
    assert not lone.held and lone.shape.batch == 1
    for r in (run, whole):
        assert r["gained"]["trunk_programs"] == 1
        assert r["gained"]["lazy_misses"] == 0
    assert _row_values(run["rows"]) == _row_values(whole["rows"])


@pytest.mark.parametrize("fault", ["writer", "planner"])
def test_a_failure_in_a_later_window_stops_the_call_and_resumes(
        window_runs, tmp_path, monkeypatch, fault):
    """(e) A flush that fails while window 1 is being drained, or a
    tokenizer that fails on a cell of window 2 (on the fill thread),
    re-raises on the caller's thread; what was flushed before stays
    done, and a resumed call scores exactly the rest."""
    from lir_tpu.data import schemas
    from lir_tpu.engine import sweep as sweep_mod

    engine = window_runs["doc-engine"]
    traffic = window_runs["traffic"]["doc"]
    out = tmp_path / "rows.csv"
    if fault == "writer":
        real_write = schemas.write_perturbation_results
        calls = {"n": 0}

        def failing_write(rows, path, append=True):
            calls["n"] += 1
            if calls["n"] == 2:
                raise OSError("disk full")
            return real_write(rows, path, append=append)

        monkeypatch.setattr(sweep_mod.schemas, "write_perturbation_results",
                            failing_write)
    else:
        engine.tokenizer.poison = grid_mod.build_grid(
            "m", *traffic)[12].confidence_prompt       # prompt 2
    run = _swept(engine, traffic, out, checkpoint_every=4)
    monkeypatch.undo()
    engine.tokenizer.poison = None
    assert str(run["error"]) == ("disk full" if fault == "writer"
                                 else "tokenizer: poisoned text")
    done = len(schemas.read_results_frame(out))
    # A flush follows the dispatch that fills it: 1 + 4 rows, then 4.
    # The writer's second flush fails; the planner's failure lets the
    # two windows before it drain (10 cells: both flushes, one row left).
    assert done == (5 if fault == "writer" else 9)
    if fault == "planner":
        assert len(run["windows"]) == 2      # the poisoned window never came
    resumed = _swept(engine, traffic, out, checkpoint_every=4)
    assert resumed["error"] is None
    assert len(resumed["rows"]) == 21 - done
    frame = schemas.read_results_frame(out)
    assert len(frame) == 21 and len(set(frame["Rephrased Main Part"])) == 21
    # What the resumed call scored is what the broken one had not flushed
    # (a row left alone by the resume rides another program: its values
    # are its cell's to rounding, not bitwise).
    flushed = set(frame["Rephrased Main Part"][:done])
    assert {r.rephrased_main for r in resumed["rows"]} == set(
        frame["Rephrased Main Part"]) - flushed


@pytest.mark.parametrize("shape", ["doc", "doc-whole", "short"])
def test_the_fill_counters_say_what_ran_behind_the_device(window_runs,
                                                          shape):
    """(f) Seconds of fill behind the device never exceed the seconds of
    fill; a call that is one window has none, and the counters are in
    the registry's snapshot either way (0, not missing)."""
    from lir_tpu.observe import registry as metrics_mod

    gained = window_runs[shape]["gained"]
    assert gained["fill_s"] > 0 and gained["windows"] >= 1
    if shape == "doc":
        assert (gained["windows"], gained["windows_ahead"]) == (5, 4)
        assert 0 < gained["ahead_s"] < gained["fill_s"]
    else:
        assert (gained["windows"], gained["windows_ahead"]) == (1, 0)
        assert gained["ahead_s"] == 0
    engine = window_runs["short-engine" if shape == "short"
                         else "doc-engine"]
    snap = metrics_mod.engine_registry(engine).snapshot(device_memory=False)
    fields = snap["sources"]["fill"]["fields"]
    assert set(fields) == {"fill_s", "ahead_s", "windows", "windows_ahead",
                           "wait_s"}
    assert 0 <= fields["ahead_s"] <= fields["fill_s"]
    spans = snap["sources"]["spans"]["summary"]
    if shape == "short":
        assert fields["ahead_s"] == 0 and fields["windows_ahead"] == 0
    else:
        assert spans["sweep/plan_ahead"]["count"] >= 4
