"""The control: the reference one precision step below the configuration
reads above the limit that the program's runs keep under (tiny sizes; the
readings at the cells' own sizes are in PERF.md), read alone and through a
whole run of the harness."""

import jax
import numpy as np
import pytest

from bench.arch import qwen2 as A
from harness_util import TINY


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fp8_control_fails_the_limit(seed):
    sp = A.model_spec(TINY)
    w = A.make_weights(sp, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, 256, 24).astype(np.int32)
    served = rng.integers(0, 256, 40).astype(np.int32)
    assert A.control_gap(sp, w, prompt, served) > \
        TINY["check"]["max_logit_gap"]


def test_control_through_the_harness_is_not_correct(tmp_path, monkeypatch):
    """A whole closed-loop run with its priming ticks, the control read on
    the run's own sample and judged as the program is: the program comes
    out correct and the control does not."""
    from bench import run
    from harness_util import make_root

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    res = run.run_cell("tiny.closed", 2 ** 31 + 7, 2.0, False,
                       root=make_root(tmp_path), control=True)
    assert res["correct"] is True, res["compared"]
    assert res["control"]["correct"] is False, res["control"]
    gap = res["control"]["compared"]["logit_gap"]
    assert gap["value"] > gap["limit"]
