"""Elastic multi-process training recovery — leases, peer-loss
detection, deterministic re-bootstrap.

The reference's data-parallel protocol simply HANGS when a machine
drops out mid-training: every ``Network::Allreduce`` blocks on the
dead socket until the operator notices (PAPERS.md §data-parallel; the
socket linker has no liveness story at all).  jax.distributed inherits
the same failure shape — a lost process leaves the survivors blocked
inside a collective forever.  This module adds the three pieces that
turn a hang into a bounded-window recovery:

* **file leases** (:class:`LeaseBoard`) — every worker atomically
  rewrites its ``lease_rank<r>.json`` on a heartbeat period; a peer
  whose lease goes stale past ``lease_timeout_s`` is declared dead.
  Leases are files, not sockets, because the coordinator-side liveness
  surface must survive exactly the failure being detected (a dead
  worker can't FIN its socket cleanly out of ``os._exit``).
* **peer-loss abort** (:class:`HeartbeatMonitor`) — a daemon thread per
  worker beats its own lease and watches the others.  On a stale peer
  it publishes a ``fleet.peer_lost`` event, exports the process's obs
  artifacts (best effort), and ``os._exit(EXIT_PEER_LOST)`` — the ONLY
  honest way out, since the main thread is wedged inside a collective
  the dead peer will never join.
* **deterministic re-bootstrap** (:class:`ElasticCoordinator`) — a
  parent process spawns the N workers (the subprocess harness the
  multihost tests pioneered), watches for any death, reaps the rest,
  and respawns the fleet on a FRESH coordinator port.  Respawned
  workers auto-resume from the newest intact PR-6 checkpoint bundle
  (``cli._find_resume_point``), so the recovered run reproduces the
  uninterrupted run's model text **byte-identically** — recovery is a
  pure recompute of the iterations since the last bundle, never an
  approximation (tools/chaos.py ``trainer_worker_kill``).

Fault seam: workers fire ``peer_dead`` (utils/faults.py) at every
iteration boundary with site ``rank<r>:iter<i>``, so a chaos plan kills
a specific rank at a specific iteration deterministically.  The
coordinator arms the plan for the FIRST generation only — the respawn
models a replaced node, not a haunted one.
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from ..utils import fileio
from ..utils.log import log_info, log_warning

EXIT_PEER_LOST = 96     # a survivor that aborted on a stale peer lease
LEASE_PREFIX = "lease_rank"


class PeerLostError(RuntimeError):
    """A peer worker's lease went stale (its process is gone or
    wedged); the run must re-bootstrap from the last bundle."""


# ---------------------------------------------------------------------------
# leases
# ---------------------------------------------------------------------------


class LeaseBoard:
    """Per-rank lease files under one shared directory.

    A lease carries ``{rank, pid, beat, iteration, t_wall}`` and is
    rewritten atomically (tmp+fsync+rename) each heartbeat, so a reader
    never sees a torn lease — a lease is either the previous beat or
    the current one.  Staleness is judged on wall clock (the workers
    share a host or a fleet with sane NTP; the timeout is seconds, not
    milliseconds)."""

    def __init__(self, leases_dir: str, rank: int, world: int,
                 timeout_s: float = 3.0):
        self.dir = str(leases_dir)
        self.rank = int(rank)
        self.world = int(world)
        self.timeout_s = float(timeout_s)
        self.beats = 0
        self._t_start = time.time()
        os.makedirs(self.dir, exist_ok=True)

    def _path(self, rank: int) -> str:
        return os.path.join(self.dir, f"{LEASE_PREFIX}{rank}.json")

    def beat(self, iteration: int = -1) -> None:
        self.beats += 1
        payload = {"rank": self.rank, "pid": os.getpid(),
                   "beat": self.beats, "iteration": int(iteration),
                   "t_wall": time.time()}
        fileio.atomic_write_bytes(self._path(self.rank),
                                  json.dumps(payload).encode("utf-8"),
                                  site="lease")

    def read(self, rank: int) -> Optional[dict]:
        try:
            with open(self._path(rank)) as fh:
                return json.load(fh)
        except (OSError, ValueError):
            return None

    def stale_peers(self, now: Optional[float] = None) -> List[int]:
        """Ranks whose lease is older than ``timeout_s`` (or absent
        after an initial grace of one timeout from board start — a peer
        that never managed a first beat is just as dead)."""
        now = time.time() if now is None else now
        dead = []
        for r in range(self.world):
            if r == self.rank:
                continue
            lease = self.read(r)
            if lease is None:
                if now - self._t_start > self.timeout_s:
                    dead.append(r)
            elif now - float(lease.get("t_wall", 0.0)) > self.timeout_s:
                dead.append(r)
        return dead

    def wait_stale(self, extra_wait_s: Optional[float] = None) -> List[int]:
        """Block up to ``extra_wait_s`` (default 2x the lease timeout)
        for ANY peer lease to go stale; returns the dead ranks (empty =
        every peer stayed fresh).  The survivor's verdict call: a
        collective that failed under it is a peer loss when this
        returns dead ranks, a genuine crash otherwise."""
        deadline = time.monotonic() + (2.0 * self.timeout_s
                                       if extra_wait_s is None
                                       else float(extra_wait_s))
        while True:
            dead = self.stale_peers()
            if dead or time.monotonic() >= deadline:
                return dead
            time.sleep(min(self.timeout_s / 4.0, 0.25))

    def fresh_ranks(self, now: Optional[float] = None) -> List[int]:
        """Ranks with a currently-fresh lease (the coordinator's
        recovery probe: re-bootstrap is DONE when every rank beats)."""
        now = time.time() if now is None else now
        out = []
        for r in range(self.world):
            lease = self.read(r)
            if lease is not None and \
                    now - float(lease.get("t_wall", 0.0)) <= self.timeout_s:
                out.append(r)
        return out


class HeartbeatMonitor:
    """Daemon thread: beat own lease, watch peers, abort on loss.

    The beat signals *process liveness*, deliberately not training
    progress: a worker blocked in a collective is alive and must keep
    its lease while the protocol decides who actually died.  Detection
    latency is bounded by ``timeout_s + period`` (period defaults to a
    quarter of the timeout)."""

    def __init__(self, board: LeaseBoard, *,
                 period_s: Optional[float] = None,
                 obs_export_dir: str = "",
                 on_peer_lost=None):
        self.board = board
        self.period_s = (max(board.timeout_s / 4.0, 0.05)
                         if period_s is None else float(period_s))
        self.obs_export_dir = str(obs_export_dir or "")
        self.on_peer_lost = on_peer_lost
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop,
                                        name="elastic-heartbeat",
                                        daemon=True)
        self.lost: List[int] = []

    def start(self) -> "HeartbeatMonitor":
        self.board.beat()
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)

    def _loop(self) -> None:
        while not self._stop.wait(self.period_s):
            try:
                self.board.beat()
                dead = self.board.stale_peers()
            except OSError:
                # leases dir torn down under us: the coordinator reaps
                # the board after the fleet finishes, and this daemon
                # thread may still be mid-beat — that is shutdown, not a
                # crash (must not surface as an unhandled_thread_exception
                # forensic bundle)
                return
            if dead:
                self.lost = dead
                self._abort(dead)
                return

    def _abort(self, dead: List[int]) -> None:
        from ..obs import events as obs_events

        obs_events.publish(
            "fleet.peer_lost",
            f"rank(s) {dead} lease stale past "
            f"{self.board.timeout_s:.1f}s — aborting for re-bootstrap",
            severity="error", dead_ranks=list(dead),
            rank=self.board.rank,
            lease_timeout_s=self.board.timeout_s)
        log_warning(f"elastic: rank {self.board.rank} lost peer(s) "
                    f"{dead}; exiting {EXIT_PEER_LOST} for re-bootstrap")
        if self.obs_export_dir:
            # the survivor's last will: its span/metrics/event artifacts
            # join the fleet-merged trace even though the process dies
            # with a wedged main thread (best effort, never blocking the
            # exit on an export failure)
            try:
                from ..obs import agg as obs_agg

                obs_agg.export_process_artifacts(self.obs_export_dir)
            except Exception:   # noqa: BLE001
                pass
        if self.on_peer_lost is not None:
            self.on_peer_lost(dead)
            return
        # the main thread is (typically) wedged inside a collective the
        # dead peer will never join — a clean unwind does not exist
        os._exit(EXIT_PEER_LOST)


# ---------------------------------------------------------------------------
# coordinator
# ---------------------------------------------------------------------------


@dataclass
class ElasticConfig:
    """Knobs of one elastic run (mirrored by the ``elastic_*`` names in
    config.py for CLI visibility; defaults match)."""

    world: int = 2                   # worker processes
    devices_per_proc: int = 2        # virtual CPU devices per worker
    lease_timeout_s: float = 3.0     # staleness bound (detection window)
    max_restarts: int = 2            # re-bootstraps before giving up
    restart_backoff_s: float = 0.25  # jittered exponential base
    worker_timeout_s: float = 300.0  # hard per-generation wall bound
    grace_s: float = 0.0             # wait for survivors to self-abort
                                     # (0 = 3 lease timeouts)
    shrink_on_loss: bool = False     # partial-fleet loss: respawn the
                                     # SURVIVORS as a smaller world
                                     # instead of replacing the dead
                                     # rank (pod semantics — a lost
                                     # host stays lost; shard ranges
                                     # and the mesh re-derive from the
                                     # new (rank, world))

    def __post_init__(self):
        self.world = max(int(self.world), 1)
        self.devices_per_proc = max(int(self.devices_per_proc), 1)
        self.lease_timeout_s = max(float(self.lease_timeout_s), 0.2)
        self.max_restarts = max(int(self.max_restarts), 0)
        self.restart_backoff_s = max(float(self.restart_backoff_s), 0.0)
        if self.grace_s <= 0:
            self.grace_s = 3.0 * self.lease_timeout_s

    @classmethod
    def from_config(cls, config, **over) -> "ElasticConfig":
        """Map the global Config's ``elastic_*`` knobs (the CLI-visible
        form, BASELINE.md "Fault-tolerant fleet") onto an ElasticConfig;
        ``over`` wins for harness-specific fields (world, device
        count)."""
        kw = dict(lease_timeout_s=config.elastic_lease_timeout_s,
                  max_restarts=config.elastic_max_restarts)
        kw.update(over)
        return cls(**kw)


@dataclass
class ElasticResult:
    ok: bool
    restarts: int
    generations: List[List[int]] = field(default_factory=list)
    worlds: List[int] = field(default_factory=list)
    recovery_s: Optional[float] = None
    peer_lost_exits: int = 0
    outputs: List[str] = field(default_factory=list)

    def to_dict(self) -> dict:
        return {"ok": self.ok, "restarts": self.restarts,
                "generations": self.generations,
                "worlds": self.worlds,
                "recovery_s": self.recovery_s,
                "peer_lost_exits": self.peer_lost_exits}


class ElasticCoordinator:
    """Spawn/watch/re-bootstrap loop over the elastic worker module.

    ``worker_args`` is the ``key=value`` argv passed through to
    ``python -m lightgbmv1_tpu.parallel.elastic_worker`` (data path,
    iteration count, snapshot freq, model output — see that module);
    the coordinator owns rank/port/world/lease wiring.  ``fault_env``
    (e.g. a ``peer_dead`` kill plan in ``LGBMV1_FAULTS``) is applied to
    the FIRST generation only."""

    def __init__(self, workdir: str, worker_args: Dict[str, object],
                 config: Optional[ElasticConfig] = None,
                 fault_env: Optional[Dict[str, str]] = None,
                 env: Optional[Dict[str, str]] = None):
        self.workdir = str(workdir)
        self.worker_args = dict(worker_args)
        self.config = config or ElasticConfig()
        self.fault_env = dict(fault_env or {})
        self.base_env = dict(env) if env is not None else dict(os.environ)
        os.makedirs(self.workdir, exist_ok=True)

    # -- spawn one generation -------------------------------------------
    def _spawn(self, generation: int, port: int,
               world: Optional[int] = None) -> List[subprocess.Popen]:
        """Start one generation of workers.  Workers run on virtual CPU
        devices (``JAX_PLATFORMS=cpu`` unless the caller's env names
        another platform): this rig puts N jax.distributed processes on
        ONE host, and a chip belongs to one process at a time — on a
        one-chip host N workers could not share it.  The recovery
        protocol under test (leases, abort, re-bootstrap, resume) is
        platform-independent; a real multi-host fleet passes its own
        ``env``."""
        cfg = self.config
        world = cfg.world if world is None else int(world)
        procs = []
        for rank in range(world):
            env = dict(self.base_env)
            env["PYTHONPATH"] = (
                os.path.dirname(os.path.dirname(
                    os.path.dirname(os.path.abspath(__file__))))
                + os.pathsep + env.get("PYTHONPATH", ""))
            env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                                f"{cfg.devices_per_proc}")
            env.setdefault("JAX_PLATFORMS", "cpu")
            env.setdefault("LGBMV1_OBS_ROLE", f"trainer-r{rank}")
            if generation == 0 and self.fault_env:
                env.update(self.fault_env)
            else:
                env.pop("LGBMV1_FAULTS", None)
            args = [sys.executable, "-m",
                    "lightgbmv1_tpu.parallel.elastic_worker",
                    f"rank={rank}", f"world={world}", f"port={port}",
                    f"leases_dir={os.path.join(self.workdir, 'leases')}",
                    f"lease_timeout_s={cfg.lease_timeout_s}",
                    f"generation={generation}"]
            args += [f"{k}={v}" for k, v in self.worker_args.items()]
            procs.append(subprocess.Popen(
                args, env=env, cwd=self.workdir,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True))
        return procs

    @staticmethod
    def _reap(procs: List[subprocess.Popen], grace_s: float) -> None:
        """SIGTERM the stragglers, escalate to SIGKILL after a grace —
        a survivor wedged inside a gloo collective may not honor TERM."""
        deadline = time.monotonic() + grace_s
        for p in procs:
            if p.poll() is None:
                try:
                    p.terminate()
                except OSError:
                    pass
        for p in procs:
            while p.poll() is None and time.monotonic() < deadline:
                time.sleep(0.05)
            if p.poll() is None:
                try:
                    p.kill()
                except OSError:
                    pass
                p.wait()

    def _clear_leases(self) -> None:
        leases = os.path.join(self.workdir, "leases")
        try:
            for name in os.listdir(leases):
                if name.startswith(LEASE_PREFIX):
                    os.remove(os.path.join(leases, name))
        except OSError:
            pass

    # -- the recovery loop ----------------------------------------------
    def run(self) -> ElasticResult:
        from .cluster import find_free_port

        cfg = self.config
        result = ElasticResult(ok=False, restarts=0)
        t_detect: Optional[float] = None
        world = cfg.world
        for generation in range(cfg.max_restarts + 1):
            self._clear_leases()
            port = find_free_port()
            log_info(f"elastic: generation {generation} starting "
                     f"({world} workers, coordinator :{port})")
            procs = self._spawn(generation, port, world)
            result.worlds.append(world)
            if t_detect is not None and result.recovery_s is None:
                # recovery window closes when every respawned rank has a
                # fresh lease — the fleet is re-bootstrapped and training
                board = LeaseBoard(os.path.join(self.workdir, "leases"),
                                   rank=-1, world=world,
                                   timeout_s=cfg.lease_timeout_s)
                probe_deadline = time.monotonic() + cfg.worker_timeout_s
                while time.monotonic() < probe_deadline:
                    if len(board.fresh_ranks()) == world:
                        result.recovery_s = round(
                            time.monotonic() - t_detect, 3)
                        break
                    if any(p.poll() is not None for p in procs):
                        break
                    time.sleep(0.05)
            deadline = time.monotonic() + cfg.worker_timeout_s
            rcs: List[Optional[int]] = [None] * world
            first_death: Optional[float] = None
            while time.monotonic() < deadline:
                for i, p in enumerate(procs):
                    if rcs[i] is None and p.poll() is not None:
                        rcs[i] = p.returncode
                        if p.returncode != 0 and first_death is None:
                            first_death = time.monotonic()
                done = [rc is not None for rc in rcs]
                if all(done):
                    break
                if first_death is not None and \
                        time.monotonic() - first_death > cfg.grace_s:
                    # survivors got their lease window to self-abort
                    # (EXIT_PEER_LOST); whoever is left gets reaped
                    break
                time.sleep(0.05)
            self._reap(procs, grace_s=2.0)
            outs = []
            for i, p in enumerate(procs):
                try:
                    out = p.stdout.read() if p.stdout else ""
                except (OSError, ValueError):
                    out = ""
                outs.append(out)
                if rcs[i] is None:
                    rcs[i] = p.returncode
            result.outputs = outs
            result.generations.append([int(rc) for rc in rcs])
            result.peer_lost_exits += sum(
                1 for rc in rcs if rc == EXIT_PEER_LOST)
            if all(rc == 0 for rc in rcs):
                result.ok = True
                return result
            if generation >= cfg.max_restarts:
                log_warning(f"elastic: generation {generation} failed "
                            f"(exits {rcs}) and max_restarts reached")
                return result
            if t_detect is None:
                t_detect = (first_death if first_death is not None
                            else time.monotonic())
            result.restarts += 1
            if cfg.shrink_on_loss:
                # partial-fleet loss (ISSUE 16): ranks that died HARD
                # (not the EXIT_PEER_LOST self-aborts — those survivors
                # are respawnable) are lost hosts; the next generation
                # runs the smaller world, and every worker re-derives
                # its shard range and mesh from the new (rank, world)
                # positive exits only: negative rcs are the coordinator's
                # own reap of wedged-but-alive survivors, not lost hosts
                hard_dead = sum(1 for rc in rcs
                                if rc not in (0, EXIT_PEER_LOST) and rc > 0)
                if 0 < hard_dead < world:
                    world -= hard_dead
                    log_warning(f"elastic: {hard_dead} worker(s) died "
                                f"hard; shrinking the fleet to {world} "
                                "survivors for the next generation")
            jitter = random.Random(1_000_003 * generation).random()
            delay = cfg.restart_backoff_s * (2 ** generation) \
                * (1.0 + jitter)
            log_warning(f"elastic: generation {generation} lost worker(s) "
                        f"(exits {rcs}); re-bootstrapping in {delay:.2f}s "
                        "from the newest checkpoint bundle")
            time.sleep(delay)
        return result
