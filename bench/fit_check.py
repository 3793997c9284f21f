"""Compile a configuration's serving programs for a described TPU v5e and
print what each needs in device memory.

    JAX_PLATFORMS=cpu python3 bench/fit_check.py <config> [n_pages] [programs]

No chip is needed: the TPU compiler runs here for a described (not
attached) ``v5e:2x2`` topology, on shapes only.  The weights and the page
pool are arguments of every tick program, so each program's total (its
arguments, outputs and temporaries, less what it donates) is what one chip
must hold while it runs.  Configuration files quote these numbers.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(name: str, n_pages=None, only=None) -> None:
    import dataclasses

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import run
    from repro.models.model import init_paged_pool

    jax.config.update("jax_enable_compilation_cache", False)
    manifest = run.load_manifest()
    conf = next(c for c in manifest["configs"] if c["name"] == name)
    doc = run.load_doc(run.ROOT / conf["file"])
    arch = run.load_arch(run.ROOT, doc)
    spec = arch.model_spec(doc)
    small = dict(doc, serve=dict(doc["serve"], n_pages=8))
    w = arch.weight_shapes(spec)
    cfg, serve, sched = run.build(arch, small, spec, w)
    real = dataclasses.replace(
        serve, n_pages=n_pages or doc["serve"].get("n_pages"))
    n_pages = real.resolved_n_pages()
    sched._pool = jax.eval_shape(lambda: init_paged_pool(
        sched.cfg, real.max_slots, real.max_len, n_pages, real.page_len,
        dtype=sched.cfg.dtype))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def place(t):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), t)

    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"     # kernels take their TPU branch
    try:
        progs = sched.audit_programs()
        rows = {}
        for pname, (fn, args) in progs.items():
            if pname in ("cow", "admit_hit", "snap") or (
                    only and pname not in only):
                continue
            c = fn.lower(*place(args)).compile()
            m = c.memory_analysis()
            tot = (m.argument_size_in_bytes + m.output_size_in_bytes
                   + m.temp_size_in_bytes - m.alias_size_in_bytes)
            rows[pname] = {"arguments": m.argument_size_in_bytes,
                           "outputs": m.output_size_in_bytes,
                           "temporaries": m.temp_size_in_bytes,
                           "donated": m.alias_size_in_bytes, "total": tot,
                           "kernel": "tpu_custom_call" in c.as_text()}
            print(pname, json.dumps(rows[pname]), flush=True)
    finally:
        jax.default_backend = real_backend
    pool = sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(sched._pool))
    wb = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(w))
    print(json.dumps({"config": name, "n_pages": n_pages, "pool_bytes": pool,
                      "weights_bytes": wb,
                      "largest_total": max(r["total"] for r in rows.values()),
                      "chip_bytes": 16 * 1024 ** 3}))


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else None,
         sys.argv[3].split(",") if len(sys.argv) > 3 else None)
