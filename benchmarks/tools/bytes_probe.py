"""Read a cell's device bytes from the chip's own compiler, without a chip.

Compiles the histogram pass (``hist_leaves_pallas``: 64 slots, 64 bins,
bf16; and the 1-slot bf16x2 root pass) for a *described* ``v5e:2x2`` device
at a configuration's real shape and prints ``memory_analysis()``: the bytes
of that one program's arguments, result and temporaries.  The step holds
more than one pass (scores, gradients, partition state): compare with the
``train.device_peak_bytes`` a chip run reads (PERF.md section 4).

    JAX_PLATFORMS=cpu python3 benchmarks/tools/bytes_probe.py \
        [--config mslr-lambdarank-255l-63b] [--rows-per-chip N] [--features F]

Run by hand; nothing imports it.  Nothing runs on a device here, so this
says nothing about times or results.
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(HERE))


def probe(name: str, rows: int, features: int, bins: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from lightgbmv1_tpu.ops.hist_pallas import hist_leaves_pallas

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    args = (jax.ShapeDtypeStruct((features, rows), jnp.uint8, sharding=one),
            jax.ShapeDtypeStruct((rows, 3), jnp.float32, sharding=one),
            jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one))
    out = {"config": name, "rows_per_chip": rows, "features": features,
           "bins_bytes": rows * features}
    for label, slots, precision in (("pass_64slots_bf16", 64, "bf16"),
                                    ("pass_1slot_bf16x2", 1, "bf16x2")):
        fn = functools.partial(hist_leaves_pallas, num_leaves=slots,
                               num_bins=bins, precision=precision)
        mem = jax.jit(fn).lower(*args).compile().memory_analysis()
        out[label] = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes)}
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="")
    ap.add_argument("--rows-per-chip", type=int, default=0)
    ap.add_argument("--features", type=int, default=0,
                    help="another width than the configuration's: a shape "
                    "that has no file yet")
    args = ap.parse_args()
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    paths = sorted(glob.glob(os.path.join(HERE, "configs", "*.json")))
    for path in paths:
        with open(path) as fh:
            cfg = json.load(fh)
        if args.config and cfg["name"] != args.config:
            continue
        shards = int(cfg["params"].get("num_shards", 1) or 1)
        rows = args.rows_per_chip or int(cfg["data"]["rows"]) // shards
        print(json.dumps(probe(cfg["name"], rows,
                               args.features or int(cfg["data"]["features"]),
                               int(cfg["params"]["max_bin"]) + 1)),
              flush=True)


if __name__ == "__main__":
    main()
