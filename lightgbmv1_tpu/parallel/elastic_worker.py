"""Elastic training worker — one rank of an elastic fleet.

Launched by :class:`~lightgbmv1_tpu.parallel.elastic.ElasticCoordinator`
as ``python -m lightgbmv1_tpu.parallel.elastic_worker key=value ...``.
Composes the pieces the recovery contract names:

* ``cluster.init_cluster`` — jax.distributed bootstrap (gloo CPU
  collectives + jittered retry);
* ``dist_data.load_distributed`` — this rank's row shard with globally
  agreed bins, RELOADED identically on every re-bootstrap (the shard is
  a pure function of (file, rank, world));
* PR-6 checkpoint bundles — rank 0 writes
  ``<model_out>.ckpt_iter_<k>`` every ``snapshot_freq`` iterations
  (training is implicitly barriered by the per-iteration collectives,
  so a bundle at iteration k means EVERY rank completed k); on respawn
  every rank resumes bit-exactly from the newest intact bundle via the
  CLI's validated resume-point scan;
* ``elastic.LeaseBoard`` heartbeats + peer-loss abort
  (``EXIT_PEER_LOST``), so a dead peer costs a bounded detection
  window instead of an infinite collective hang.

Fault seam: ``faults.fire("peer_dead", site="rank<r>:iter<i>")`` at
every iteration boundary — a chaos plan with ``mode="kill"`` and a
matching site is THE deterministic kill-at-k (utils/faults.py arms it
from ``LGBMV1_FAULTS``; the armed flight recorder dumps the worker's
forensic bundle on the way out).

argv keys: ``rank world port leases_dir lease_timeout_s generation
data model_out iterations snapshot_freq num_leaves min_data_in_leaf
seed objective``.
"""

from __future__ import annotations

import os
import sys


def _parse_kv(argv):
    out = {}
    for a in argv:
        k, _, v = a.partition("=")
        out[k] = v
    return out


def main(argv) -> int:
    kv = _parse_kv(argv)
    rank = int(kv["rank"])
    world = int(kv["world"])
    port = kv["port"]
    import jax

    from ..obs import dump as obs_dump
    from ..obs import events as obs_events

    obs_events.set_identity(role=os.environ.get(
        "LGBMV1_OBS_ROLE", f"trainer-r{rank}"))
    crash_dir = os.environ.get("LGBMV1_CRASH_DIR", "")
    if crash_dir:
        obs_dump.arm(crash_dir)
    if os.environ.get("LGBMV1_OBS_DIR", ""):
        # span tracer armed so the per-iteration spans land in this
        # rank's artifact — the fleet-merged Perfetto trace gets one
        # lane per worker (obs/agg.py)
        from ..obs import trace as obs_trace

        obs_trace.arm()

    from .cluster import init_cluster

    init_cluster(coordinator_address=f"127.0.0.1:{port}",
                 num_processes=world, process_id=rank)

    from ..basic import Booster, Dataset
    from ..cli import _find_resume_point, _prune_snapshots
    from ..config import Config
    from ..parallel.dist_data import load_distributed
    from ..utils import faults
    from ..utils.log import log_info
    from .elastic import EXIT_PEER_LOST, HeartbeatMonitor, LeaseBoard

    params = {
        "objective": kv.get("objective", "binary"),
        "num_leaves": int(kv.get("num_leaves", 7)),
        "min_data_in_leaf": int(kv.get("min_data_in_leaf", 20)),
        "tree_learner": "data" if world > 1 else "serial",
        "enable_bundle": False,
        "seed": int(kv.get("seed", 7)),
        "verbosity": -1,
    }
    if world > 1 and kv.get("collective"):
        # pod-scale passthrough (ISSUE 16): the hierarchical two-level
        # collective over the real process fleet (one host row per rank).
        # num_hosts falls back to the CURRENT world so a shrunk fleet
        # rebuilds a valid (host, chip) mesh without coordinator help.
        params["data_parallel_collective"] = kv["collective"]
        params["num_hosts"] = int(kv.get("num_hosts", 0)) or world
    cfg = Config.from_dict(params)
    # shard reload: each generation re-derives exactly this rank's rows
    # + the globally agreed bin mappers from the immutable data file (or,
    # for a block cache, this rank's manifest shard range — re-derived
    # from the CURRENT (rank, world), so a shrunk fleet repartitions)
    binned = load_distributed(kv["data"], cfg)

    model_out = kv["model_out"]
    iterations = int(kv.get("iterations", 8))
    snapshot_freq = int(kv.get("snapshot_freq", 2))

    booster = Booster(params=params,
                      train_set=Dataset.from_binned(binned, params=params))
    done_iters = 0
    if not os.path.exists(model_out):
        kind, path, done_iters, bundle = _find_resume_point(model_out)
        if kind == "ckpt":
            booster.resume_from_checkpoint(bundle)
            log_info(f"elastic worker {rank}: resumed bit-exactly from "
                     f"{path} ({done_iters} iterations done)")
        else:
            done_iters = 0

    board = LeaseBoard(kv["leases_dir"], rank=rank, world=world,
                       timeout_s=float(kv.get("lease_timeout_s", 3.0)))
    monitor = HeartbeatMonitor(
        board, obs_export_dir=os.environ.get("LGBMV1_OBS_DIR", "")).start()

    try:
        for i in range(done_iters, iterations):
            booster.update()
            board.beat(iteration=i + 1)
            # deterministic kill-at-k seam: a peer_dead kill plan lands
            # HERE, after iteration i+1's collectives completed everywhere
            faults.fire("peer_dead", site=f"rank{rank}:iter{i + 1}")
            if snapshot_freq > 0 and (i + 1) % snapshot_freq == 0:
                # COLLECTIVE capture on every rank (cross-process score
                # gather); one bundle on disk — rank 0's
                booster.save_checkpoint(f"{model_out}.ckpt_iter_{i + 1}",
                                        write_file=(rank == 0))
                if rank == 0:
                    _prune_snapshots(model_out, keep=2)
    except BaseException:
        # a failed collective under a dying peer is a PEER LOSS, not a
        # crash of this worker: wait out the lease window for the
        # verdict, and exit for re-bootstrap without burning a forensic
        # bundle (the killed peer's own bundle is the crash evidence).
        # No stale peer -> a genuine local crash: re-raise into the
        # armed flight recorder.
        dead = board.wait_stale()
        if not dead:
            raise
        from ..obs import events as _ev

        _ev.publish("fleet.peer_lost",
                    f"collective failed and rank(s) {dead} lease went "
                    "stale — aborting for re-bootstrap",
                    severity="error", dead_ranks=list(dead), rank=rank)
        obs_dir = os.environ.get("LGBMV1_OBS_DIR", "")
        if obs_dir:
            try:
                from ..obs import agg as obs_agg

                obs_agg.export_process_artifacts(obs_dir)
            except Exception:   # noqa: BLE001
                pass
        return EXIT_PEER_LOST
    monitor.stop()
    if monitor.lost:
        return EXIT_PEER_LOST
    if rank == 0:
        booster.save_model(model_out)

    obs_dir = os.environ.get("LGBMV1_OBS_DIR", "")
    if obs_dir:
        from ..obs import agg as obs_agg

        obs_agg.export_process_artifacts(obs_dir)
    print(f"ELASTIC RANK {rank} DONE iters={iterations}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
