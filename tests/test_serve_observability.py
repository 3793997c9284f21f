"""Observability of the serving tick (DESIGN.md §Observability).

The device programs carry named scopes (``chunk`` / ``decode`` around the
tick's two parts, ``attn`` / ``mlp`` / ``head`` inside the model) under
their unchanged jitted names; ``step_tick`` writes ``serve.*`` host spans
that nest inside a caller's span in phase order; and
``ServeScheduler.counters()`` counts the chunk slab's rows, admission
stalls, the KV pages the live slots hold and have written, as the page
tables and the device's cache lengths say, and the paged-attention
kernel's grid blocks and those it computes.
"""

import glob
import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import init_params
from repro.serving import ServeConfig
from repro.serving.kvpool import TRASH_PAGE, blocks_for_tokens
from repro.serving.scheduler import ServeScheduler

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ["serve.slab", "serve.launch", "serve.sync", "serve.bookkeep"]


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke("smollm_135m").replace(dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _sched(cfg, params, **kw):
    sc = dict(max_slots=2, max_len=64, buckets=(8, 16), chunked="auto",
              chunk_len=8, paged=True, page_len=8, tick_steps=2)
    sc.update(kw)
    return ServeScheduler(cfg, params, ServeConfig(**sc))


def _prompt(cfg, n, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n,
                                                dtype=np.int32)


def _ticks(sched):
    """Run to the end; the counters after every tick."""
    out = []
    while sched.pending:
        if not sched.step_tick():
            break
        out.append(sched.counters())
    return out


# ------------------------------------------------------------ named scopes

@pytest.mark.parametrize("name,module,parts", [
    ("mixed", "mixed_paged", ("chunk", "decode")),
    ("chunk", "chunk_paged", ("chunk",)),
    ("tick", "tick_paged", ("decode",)),
    ("prefill_b8", "prefill", ()),
])
def test_programs_carry_named_scopes(setup, name, module, parts):
    """Each program keeps its jitted name; its ops sit under the tick's
    ``chunk`` / ``decode`` scopes as it runs them, each with the model's
    ``attn``, ``mlp`` and ``head`` inside; the decode scan calls the
    ``_paged_decode_attention`` kernel under ``decode`` and ``attn``."""
    cfg, params = setup
    fn, args = _sched(cfg, params,
                      attn_kernel="pallas").audit_programs()[name]
    hlo = fn.lower(*args).compile().as_text()
    assert hlo.startswith(f"HloModule jit_{module},")
    root = f"jit({module})/"
    paths = [n[len(root):].split("/")
             for n in re.findall(r'op_name="([^"]+)"', hlo)
             if n.startswith(root)]
    assert {"chunk", "decode"} & {p[0] for p in paths} == set(parts)
    for part in parts or (None,):
        under = [p for p in paths if part is None or p[0] == part]
        for scope in ("attn", "mlp", "head"):
            assert any(scope in p for p in under), (part, scope)
    kernel = [p for p in paths if "jit(_paged_decode_attention)" in p]
    assert bool(kernel) == ("decode" in parts)
    assert all(p[0] == "decode" and "attn" in p for p in kernel)


# ---------------------------------------------------------------- counters

def test_chunk_counters_count_fed_tokens_and_slab_rows(setup):
    """``chunk_tokens`` is every prompt token fed through the slab (all of
    them under ``chunked="always"``); ``chunk_slab_rows`` is
    ``max_slots x chunk_len`` for each call of a program with a chunk."""
    cfg, params = setup
    sched = _sched(cfg, params, chunked="always")
    calls = []
    for attr in ("_mixed", "_chunk"):
        fn = getattr(sched, attr)
        setattr(sched, attr,
                lambda *a, _fn=fn: calls.append(None) or _fn(*a))
    lens = (3, 8, 13, 20, 9)
    for i, n in enumerate(lens):
        sched.submit(_prompt(cfg, n, i), max_new=3)
    seen = _ticks(sched)
    c = seen[-1]
    assert c["ticks"] == len(seen)
    assert c["chunk_tokens"] == sum(lens)
    assert c["chunk_slab_rows"] == len(calls) * 2 * 8
    assert c["admit_stalls"] == 0
    # the gather path runs no kernel
    assert c["attn_kv_blocks_grid"] == c["attn_kv_blocks_live"] == 0


def test_admit_stalls_count_ticks_waiting_on_the_pool(setup):
    """A request that a free slot could take but the page pool cannot yet
    hold stalls admission once per tick until a retirement frees pages; a
    request queued behind busy slots is no stall."""
    cfg, params = setup
    # each request holds ceil((12 + 6 + 2) / 8) = 3 of the 4 usable pages
    # for 3 ticks, so the three run one after another
    sched = _sched(cfg, params, n_pages=5)
    for i in range(3):
        sched.submit(_prompt(cfg, 12, i), max_new=6)
    stalls = [c["admit_stalls"] for c in _ticks(sched)]
    assert stalls == [1, 2, 3, 4, 5, 6, 6, 6, 6]
    assert all(r.finish_reason == "length" for r in sched.run())

    sched = _sched(cfg, params, max_slots=1)
    for i in range(3):
        sched.submit(_prompt(cfg, 12, i), max_new=6)
    assert _ticks(sched)[-1]["admit_stalls"] == 0


def test_kv_page_gauges_match_the_tables(setup):
    """On every tick ``kv_pages_reserved`` is the distinct pages of the
    live slots' page tables (a shared prefix page counts once) and
    ``kv_pages_written`` those that hold a token below the slot's cache
    length on the device; written <= reserved <= capacity."""
    cfg, params = setup
    sched = _sched(cfg, params, max_slots=3, prefix_cache=True)
    base = _prompt(cfg, 24)
    sched.submit(base, max_new=4)
    sched.run()                      # donates base's pages to the cache
    for i in range(3):
        sched.submit(np.concatenate([base, _prompt(cfg, 3 + 5 * i, i)]),
                     max_new=5 + 2 * i)
    sched.submit(_prompt(cfg, 10, 7), max_new=4)
    pl = sched.page_len
    shared_seen = False
    while sched.pending:
        sched.step_tick()
        c = sched.counters()
        live = np.flatnonzero(sched._active)
        lengths = np.asarray(sched._pool["length"])
        rows = [sched._table[i] for i in live]
        reserved = [p for r in rows for p in r if p != TRASH_PAGE]
        written = {int(p) for i, r in zip(live, rows)
                   for p in r[:blocks_for_tokens(lengths[i], pl)]}
        shared_seen |= len(set(reserved)) < len(reserved)
        assert c["kv_pages_capacity"] == sched.n_pages - 1
        assert c["kv_pages_reserved"] == len(set(reserved))
        assert c["kv_pages_written"] == len(written)
        assert (c["kv_pages_written"] <= c["kv_pages_reserved"]
                <= c["kv_pages_capacity"])
    assert shared_seen
    assert sched.counters()["kv_pages_reserved"] == 0


def test_attn_block_counters_by_hand(setup):
    """``attn_kv_blocks_grid`` / ``attn_kv_blocks_live`` on a small kernel
    scheduler, worked out by hand.  page_len 8 and a 64-column table make
    a block of 16 pages (128 tokens); 3 splits pad the table to 96
    columns, 6 blocks a row; 2 slots and 2 steps a tick give 24 grid
    blocks a tick.  Both requests prefill in the first tick and decode 12
    tokens in 6 ticks.  At step ``t`` a row's kernel length is its prompt
    plus the tokens before the step plus one: 121..132 for the 120-token
    prompt (one block up to 128 tokens, two after), 6..17 for the 5-token
    one (always one)."""
    cfg, params = setup
    sched = _sched(cfg, params, max_len=512, buckets=(128,),
                   attn_kernel="pallas", attn_splits=3)
    assert sched._table.shape[1] == 64
    sched.submit(_prompt(cfg, 120), max_new=12)
    sched.submit(_prompt(cfg, 5, 1), max_new=12)
    seen = _ticks(sched)
    assert [c["attn_kv_blocks_grid"] for c in seen] == [24, 48, 72, 96,
                                                        120, 144]
    # per tick: long row 1+1 (121-122), 1+1, 1+1, 1+1 (127-128), 2+2, 2+2;
    # short row 1+1 every tick
    assert [c["attn_kv_blocks_live"] for c in seen] == [4, 8, 12, 16, 22,
                                                        28]


def test_dense_scheduler_counters_have_no_pages(setup):
    cfg, params = setup
    sched = _sched(cfg, params, paged=False)
    sched.submit(_prompt(cfg, 20), max_new=3)
    c = _ticks(sched)[-1]
    assert c["chunk_tokens"] == 20
    assert (c["kv_pages_capacity"], c["kv_pages_reserved"],
            c["kv_pages_written"]) == (0, 0, 0)


# ------------------------------------------------------------------ spans

def _host_events(trace_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("bench.", "serve.")):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, dict(e.stats)))
    return sorted(out)


def test_serve_spans_nest_inside_the_callers_tick(setup, tmp_path,
                                                   monkeypatch):
    """Under ``jax.profiler.trace`` every tick's ``serve.tick`` holds its
    admissions (with ``rid`` and ``path``) and then slab, launch, sync and
    bookkeep, all inside the caller's ``bench.tick``; the benchmark's
    reduction still finds only its own spans, one per tick."""
    cfg, params = setup
    sched = _sched(cfg, params)
    sched.submit(_prompt(cfg, 20, 1), max_new=3)        # past every bucket
    sched.submit(_prompt(cfg, 5, 2), max_new=3)
    n_ticks = 0
    jax.profiler.start_trace(str(tmp_path))
    try:
        while sched.pending:
            with jax.profiler.TraceAnnotation("bench.tick"):
                sched.step_tick()
            n_ticks += 1
    finally:
        jax.profiler.stop_trace()
    evs = _host_events(str(tmp_path))
    ticks = [e for e in evs if e[2] == "bench.tick"]
    assert len(ticks) == n_ticks
    admits = []
    for a, b, _, _ in ticks:
        inner = [e for e in evs
                 if e[2].startswith("serve.") and a <= e[0] and e[1] <= b]
        names = [e[2] for e in inner]
        assert names[0] == "serve.tick"
        n_adm = names.count("serve.admit")
        assert names[1:] == ["serve.admit"] * n_adm + PHASES
        admits += [e[3] for e in inner if e[2] == "serve.admit"]
    assert admits == [{"rid": 0, "path": "chunk"},
                      {"rid": 1, "path": "bucket"}]

    monkeypatch.syspath_prepend(REPO)
    trace = importlib.import_module("bench.lib.trace")
    assert [s.name for s in trace.load(str(tmp_path)).spans] == \
        ["bench.tick"] * n_ticks
