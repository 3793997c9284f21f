"""Architectures found by name: the interface every module in
``bench/arch/`` gives, the refusal of a configuration that names none, and
``qwen2`` reading exactly what the dense code read before it moved into
its module (values recorded before the move, on the CPU)."""

import hashlib
import inspect
import json
import re

import jax
import numpy as np
import pytest

import bench.arch
from bench import run
from bench.arch import qwen2
from harness_util import REPO, TINY, TINY2, make_root

BENCH = REPO / "bench"
DOC = bench.arch.__doc__
FUNCS = re.findall(r"^``(\w+)\((.*)\)``$", DOC, re.M)
FIELDS = set(re.findall(r"``spec\.(\w+)``", DOC))
MODULES = sorted(BENCH.glob("arch/[!_]*.py")) + [
    BENCH / "tests" / "data" / "gqa_qk_norm.py"]


def test_interface_is_written_down():
    assert [f for f, _ in FUNCS] == [
        "model_spec", "make_weights", "weight_shapes", "program_config",
        "served_gap", "control_gap", "decode_flops", "prefill_flops",
        "head_params", "paged_attn_cost"]
    assert {"name", "n_layers", "vocab_size", "dtype", "norm_eps"} <= FIELDS


@pytest.mark.parametrize("path", MODULES, ids=lambda p: p.stem)
def test_module_gives_the_interface(path):
    mod = run._load(f"conformance_{path.stem}", path)
    for name, args in FUNCS:
        fn = getattr(mod, name, None)
        assert callable(fn), f"{path.name} lacks {name}"
        documented = [a.split("=")[0] for a in args.split(", ")]
        assert list(inspect.signature(fn).parameters) == documented, name
    docs = [json.loads(p.read_text()) for p in BENCH.glob("configs/*.json")]
    docs = [d for d in docs + [TINY, TINY2] if d["model_type"] == path.stem]
    assert docs, f"no configuration names {path.stem}"
    for doc in docs:
        spec = mod.model_spec(doc)
        hash(spec)
        for field in FIELDS:
            assert hasattr(spec, field), (path.stem, field)


def test_no_architecture_read_outside_its_module():
    """Code outside ``bench/arch/`` reads only the documented spec fields and
    no weight key."""
    files = [*BENCH.glob("*.py"), *BENCH.glob("lib/*.py"),
             *BENCH.glob("metrics/*.py")]
    for f in files:
        text = f.read_text()
        assert set(re.findall(r"\bspec\.(\w+)", text)) <= FIELDS, f.name
        assert not re.search(r'"w[qkv]"', text), f.name


@pytest.mark.parametrize("model_type", [None, "no_such_arch"])
def test_configuration_without_a_module_stops_setup(tmp_path, model_type):
    conf = {k: v for k, v in TINY.items() if k != "model_type"}
    if model_type:
        conf["model_type"] = model_type
    root = make_root(tmp_path, config=conf)
    with pytest.raises(SystemExit) as e:
        run.setup_cell("tiny.mix", 1, root)
    where = root / "bench" / "arch"
    if model_type is None:
        assert '"model_type"' in str(e.value)
        assert f"{where}/<model_type>.py" in str(e.value)
    else:
        assert str(where / f"{model_type}.py") in str(e.value)


def test_qwen2_reads_as_before_the_move():
    """Weights, reference gaps and counts of ``TINY`` equal the values the
    dense code gave before it moved (weights, gaps: bit for bit)."""
    sp = qwen2.model_spec(TINY)
    w = qwen2.make_weights(sp, jax.random.PRNGKey(2 ** 31 + 5))
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(w):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == ("2c8c038f34ac01a3ec9c653439ae74b7"
                             "586093485700c3ebda10672479e0a9a0")
    rng = np.random.default_rng(12345)
    prompt = rng.integers(0, 256, 37).astype(np.int32)
    served = rng.integers(0, 256, 23).astype(np.int32)
    assert qwen2.served_gap(sp, w, prompt, served).hex() == \
        "0x1.8ac3b20000000p-1"
    assert qwen2.control_gap(sp, w, prompt, served).hex() == \
        "0x1.776f000000000p-5"
    assert qwen2.decode_flops(sp, 1234, 7) == 1893376.0
    assert qwen2.prefill_flops(sp, 10, 48, 1) == 7958528.0
    assert qwen2.paged_attn_cost(sp, [5, 17, 300], 2, 2) == (82432.0,
                                                             41984.0)
    assert qwen2.head_params(sp) == 16384


def test_readers_read_as_before_the_move():
    from test_bench_trace import _ctx, small_trace

    ctx = _ctx(small_trace())
    assert run.reader(run.ROOT, "paged_attn_roofline.tps")(ctx) == \
        1.7973137973137957e-06
    assert run.reader(run.ROOT, "tick_mfu_pct.tps")(ctx) == \
        3.0278172588832486e-08
