"""A throwaway root for CPU runs of the harness: the benchmark's files plus
tiny configurations, mixes, a metric and a second architecture added by
name, as a later change would add them (no existing file is edited)."""

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY = {
    "name": "tiny", "source": "test", "model_type": "qwen2",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "attention_bias": True, "torch_dtype": "bfloat16",
    "weights": {"quantize": False},
    "serve": {"max_slots": 4, "max_len": 128, "buckets": [16, 32],
              "chunked": "auto", "chunk_len": 16, "paged": True,
              "page_len": 16, "attn_kernel": "pallas", "tick_steps": 4},
    "check": {"max_logit_gap": 0.02},
}
# a second architecture, whose module only the test root holds:
# bench/tests/data/gqa_qk_norm.py (q/k norms, no biases, an untied head)
TINY2 = dict(TINY, name="tiny2", model_type="gqa_qk_norm",
             attention_bias=False, tie_word_embeddings=False)
MIX = {"loop": "open", "rate": 6.0, "block": 8,
       "prompt": {"median": 20, "sigma": 0.6, "min": 4, "max": 48},
       "output": {"median": 8, "sigma": 0.4, "min": 4, "max": 16},
       "drain_s": 30, "trace_s": 1, "check_requests": 4}
# more clients than slots, primed: the regime of the closed-loop cells
CLOSED = {"loop": "closed", "clients": 6, "first_wave": "residual",
          "prime_ticks": 3, "block": 6,
          "prompt": {"median": 20, "sigma": 0.6, "min": 4, "max": 48},
          "output": {"median": 10, "sigma": 0.4, "min": 4, "max": 16},
          "drain_s": 30, "trace_s": 1, "check_requests": 4}
METRIC = '''"""Requests the window attempted (a throwaway metric)."""


def read(ctx):
    return float(len(ctx.window.reqs))
'''


def make_root(tmp: Path, config=None, mix=None) -> Path:
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(config or TINY))
    (root / "bench" / "traffic" / "tinymix.json").write_text(
        json.dumps(mix or MIX))
    (root / "bench" / "traffic" / "tinyclosed.json").write_text(
        json.dumps(CLOSED))
    (root / "bench" / "metrics" / "tiny_requests.py").write_text(METRIC)
    (root / "bench" / "configs" / "tiny2.json").write_text(json.dumps(TINY2))
    shutil.copyfile(REPO / "bench" / "tests" / "data" / "gqa_qk_norm.py",
                    root / "bench" / "arch" / "gqa_qk_norm.py")
    m = json.loads((REPO / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"})
    m["workloads"].append({"name": "tiny.mix", "config": "tiny",
                           "traffic": "tinymix", "chips": 1, "why": "test"})
    m["workloads"].append({"name": "tiny.closed", "config": "tiny",
                           "traffic": "tinyclosed", "chips": 1,
                           "why": "test"})
    m["configs"].append({"name": "tiny2", "source": "test",
                         "file": "bench/configs/tiny2.json", "reduced": [],
                         "why": "test"})
    m["workloads"].append({"name": "tiny2.mix", "config": "tiny2",
                           "traffic": "tinymix", "chips": 1, "why": "test"})
    m["end_to_end"].append({"name": "tiny_requests", "unit": "requests",
                            "better": "higher", "bound": 0.25,
                            "source": "host_clock",
                            "workloads": ["tiny.mix", "tiny.closed",
                                          "tiny2.mix"]})
    # an open-loop cell below capacity brings the TTFT tail as an
    # end-to-end metric of its own, under the name of an existing reader
    m["end_to_end"].append({"name": "ttft_p95_ms", "unit": "ms",
                            "better": "lower", "bound": 0.25,
                            "source": "host_clock",
                            "workloads": ["tiny.mix", "tiny2.mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    return root
