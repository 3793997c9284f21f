"""The harness on the CPU at a tiny size: everything found by name, the
refusal without a chip, and ``correct`` coming out false when the timed
path is broken underneath."""

import json
import os
import subprocess
import sys

import pytest

from harness_util import REPO, make_root

SEED = 2 ** 31 + 101


@pytest.fixture(autouse=True)
def _no_repo_cache(monkeypatch, tmp_path):
    # CPU programs stay out of the checkout's compile cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def test_added_config_mix_and_metric_run_by_name(tmp_path):
    from bench import run

    root = make_root(tmp_path)
    res = run.run_cell("tiny.mix", SEED, 2.0, False, root=root,
                       out_dir=tmp_path / "out")
    assert res["correct"] is True, res["compared"]
    assert set(res["metrics"]) == {"output_tok_s", "ttft_p95_ms",
                                   "tpot_p95_ms", "setup_s",
                                   "tiny_requests"}
    assert res["metrics"]["tiny_requests"]["value"] == res["attempted"] > 0
    assert res["failed"] == 0
    assert res["metrics"]["output_tok_s"]["value"] > 0
    assert list(res)[-1] == "compared"
    assert (tmp_path / "out" / "requests.jsonl").exists()
    report = json.loads((tmp_path / "out" / "report.json").read_text())
    assert report["compiles_in_window"] == 0


def test_second_architecture_added_by_new_files_only(tmp_path):
    """A configuration whose ``model_type`` names a module that only the
    test root holds runs correct, and every file copied from the repository
    is byte-identical to it: nothing existing was edited."""
    from bench import run

    root = make_root(tmp_path)
    res = run.run_cell("tiny2.mix", SEED, 2.0, False, root=root)
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0
    assert res["metrics"]["tiny_requests"]["value"] == res["attempted"] > 0
    copied = [p for p in (REPO / "bench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts]
    assert copied
    for p in copied:
        assert (root / p.relative_to(REPO)).read_bytes() == p.read_bytes(), p
    # the manifest only gains entries
    m, ours = (json.loads((r / "BENCHMARK.json").read_text())
               for r in (root, REPO))
    for key, value in ours.items():
        assert (m[key][:len(value)] == value if isinstance(value, list)
                else m[key] == value), key


def test_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen14b-l12.decode-batch", "--seed", "1",
                        "--seconds", "1"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 3
    assert p.stdout == ""
    assert "TPU" in p.stderr


def _alter_tokens(sched):
    """A token altered where it is produced: the decode tick hands out the
    next id instead of the argmax."""
    tick = sched._tick

    def broken(*args):
        lg, pool, toks, fracs = tick(*args)
        return lg, pool, (toks + 1) % sched.cfg.vocab_size, fracs
    sched._tick = broken


def test_altered_token_is_not_correct(tmp_path):
    from bench import run

    res = run.run_cell("tiny.mix", SEED, 2.0, False, root=make_root(tmp_path),
                       fault=_alter_tokens)
    assert res["correct"] is False
    assert res["compared"]["logit_gap"]["value"] > 0.02


def test_state_left_unchanged_is_not_correct(tmp_path, monkeypatch):
    """A decode step that returns the KV pool unchanged: its keys and
    values are never written."""
    from bench import run
    from repro.models import attention

    monkeypatch.setattr(attention, "_paged_write",
                        lambda cache, table, x, pos, keep: cache)
    res = run.run_cell("tiny.mix", SEED, 2.0, False, root=make_root(tmp_path))
    assert res["correct"] is False
