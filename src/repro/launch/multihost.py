"""Multi-host process entry point for the CALL mesh solver.

One command serves four launch styles:

  per-process (what srun/mpirun/k8s run on every host)::

      python -m repro.launch.multihost \
          --coordinator host0:1234 --num-processes 8 --process-id $RANK \
          --store /shared/rcv1-shards --rounds 30

  single-node convenience forker (spawns N local processes wired to a
  fresh coordinator port — also what the CI multihost-smoke job runs)::

      python -m repro.launch.multihost --spawn 2 --demo --verify

  standalone coordination-service host (never joins the mesh; makes
  rank-0 loss survivable on the "kv" control plane — see
  docs/multihost.md)::

      python -m repro.launch.multihost --service-host \
          --coordinator host9:1234 --num-processes 8

  chaos harness (spawn mode + a declarative fault schedule)::

      python -m repro.launch.multihost --spawn 3 --demo --elastic \
          --chaos kill-coordinator@2,rejoin@4

  demo fixture: ``--demo`` has rank 0 write + ingest a small synthetic
  LIBSVM dataset under ``--workdir`` (the store's manifest is its
  commit marker, so the other ranks simply poll for it), then every
  rank runs the mesh trajectory over its own worker slice.

Every rank prints a ``RESULT {json}`` line with its (replicated)
trace; the spawner asserts all ranks' traces are bit-identical (a
re-admitted rank's trace must be the exact SUFFIX from its resume
round) and exits non-zero on any child failure, timeout (a hung
collective kills the job after ``--timeout`` seconds rather than
stalling), or trace divergence.  ``--verify`` additionally recomputes
the single-process `run_scanned` reference (mapping the full store —
demo scale only) on the lowest rank the chaos schedule leaves alive,
and asserts the mesh trace matches within fp32 tolerance.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ---------------------------------------------------------------------------
# Chaos schedules
# ---------------------------------------------------------------------------

def parse_chaos(spec: str) -> dict:
    """Parse a declarative fault schedule into its event table.

    Grammar — comma-separated events:

      ``kill:R@K[:barrier]``   rank R SIGKILLs itself at the chunk
                               boundary after round K (":barrier": after
                               obeying a re-mesh verdict but before the
                               re-mesh barrier — death during recovery)
      ``kill-coordinator@K``   alias for ``kill:0@K``
      ``depart:R@K``           rank R goes protocol-dead but stays up
                               (requires a matching rejoin)
      ``rejoin[:R]@K``         the killed/departed rank R announces
                               itself again once the run reaches round
                               K (R inferred when only one candidate)
      ``stop:R@T:D``           the SPAWNER SIGSTOPs rank R's process T
                               seconds in, for D seconds (slow-but-
                               alive: D must stay under the heartbeat
                               timeout, so the run finishes clean)

    A ``kill`` with a matching ``rejoin`` runs as a park/revive
    simulation (the process goes protocol-dead instead of exiting): a
    genuinely SIGKILLed process cannot re-enter a `jax.distributed`
    job, but a recovered HOST is exactly this schedule.  Returns
    ``{"kills": [(rank, round, at_barrier)], "departs": {rank: round},
    "rejoins": {rank: round}, "stops": [(rank, at_s, for_s)]}``.
    """
    kills: list = []
    departs: dict = {}
    rejoins: dict = {}
    deferred_rejoins: list = []
    stops: list = []
    for ev in spec.split(","):
        ev = ev.strip()
        if not ev:
            continue
        try:
            if ev.startswith("kill-coordinator@"):
                kills.append((0, int(ev.split("@", 1)[1]), False))
            elif ev.startswith("kill:"):
                head, k = ev[len("kill:"):].split("@", 1)
                at_barrier = k.endswith(":barrier")
                if at_barrier:
                    k = k[:-len(":barrier")]
                kills.append((int(head), int(k), at_barrier))
            elif ev.startswith("depart:"):
                r, k = ev[len("depart:"):].split("@", 1)
                departs[int(r)] = int(k)
            elif ev.startswith("rejoin:"):
                r, k = ev[len("rejoin:"):].split("@", 1)
                rejoins[int(r)] = int(k)
            elif ev.startswith("rejoin@"):
                deferred_rejoins.append(int(ev.split("@", 1)[1]))
            elif ev.startswith("stop:"):
                r, rest = ev[len("stop:"):].split("@", 1)
                at_s, for_s = rest.split(":", 1)
                stops.append((int(r), float(at_s), float(for_s)))
            else:
                raise ValueError("unknown event")
        except (ValueError, IndexError) as e:
            raise SystemExit(
                f"bad --chaos event {ev!r} ({e}); grammar: kill:R@K"
                f"[:barrier] | kill-coordinator@K | depart:R@K | "
                f"rejoin[:R]@K | stop:R@T:D") from None
    if deferred_rejoins:
        candidates = sorted(set(r for r, _, _ in kills) | set(departs))
        if len(candidates) != 1:
            raise SystemExit(
                f"--chaos: bare rejoin@K cannot infer its rank from "
                f"{len(candidates)} kill/depart candidates "
                f"{candidates}; use rejoin:R@K")
        for k in deferred_rejoins:
            rejoins[candidates[0]] = k
    return {"kills": kills, "departs": departs, "rejoins": rejoins,
            "stops": stops}


def validate_chaos(chaos: dict, *, num_processes: int, rounds: int,
                   hb_timeout: float) -> None:
    """Reject schedules that cannot do what they claim (the CLI half
    of the elastic-knob validation)."""
    def _rank_ok(r):
        if not 0 <= r < num_processes:
            raise SystemExit(f"--chaos: rank {r} out of range for "
                             f"{num_processes} processes")

    killed = {}
    for r, k, _ in chaos["kills"]:
        _rank_ok(r)
        if not 1 <= k < rounds:
            raise SystemExit(f"--chaos: kill:{r}@{k} is outside the "
                             f"{rounds}-round schedule (need 1 <= K < "
                             f"rounds, or nothing is left to recover)")
        if r in killed or r in chaos["departs"]:
            raise SystemExit(f"--chaos: rank {r} killed/departed twice")
        killed[r] = k
    for r, k in chaos["departs"].items():
        _rank_ok(r)
        if not 1 <= k < rounds:
            raise SystemExit(f"--chaos: depart:{r}@{k} is outside the "
                             f"{rounds}-round schedule")
        if r not in chaos["rejoins"]:
            raise SystemExit(f"--chaos: depart:{r}@{k} has no matching "
                             f"rejoin:{r}@K (a departed process stays "
                             f"up only to come back)")
    for r, k in chaos["rejoins"].items():
        _rank_ok(r)
        gone_at = killed.get(r, chaos["departs"].get(r))
        if gone_at is None:
            raise SystemExit(f"--chaos: rejoin:{r}@{k} without a kill "
                             f"or depart for rank {r}")
        if not gone_at < k < rounds:
            raise SystemExit(
                f"--chaos: rejoin:{r}@{k} must land strictly between "
                f"the departure round ({gone_at}) and the last round "
                f"({rounds}) — later rejoins would never be admitted")
    for r, at_s, for_s in chaos["stops"]:
        _rank_ok(r)
        if at_s < 0 or for_s <= 0:
            raise SystemExit(f"--chaos: stop:{r}@{at_s}:{for_s} needs "
                             f"T >= 0 and D > 0")
        if for_s >= hb_timeout:
            raise SystemExit(
                f"--chaos: stop:{r} pause of {for_s}s reaches the "
                f"{hb_timeout}s heartbeat timeout — the rank would be "
                f"declared dead while SIGSTOPped and re-meshed away; "
                f"the supported schedule is slow-but-alive (D < "
                f"heartbeat timeout).  Use kill:{r}@K for a death.")


def chaos_env(chaos: dict) -> dict:
    """Translate a parsed schedule into the elastic driver's fault-
    injection env vars (`KILL_ENV` / `DEPART_ENV`).

    Kills WITH a matching rejoin become the park/revive DEPART entry;
    the rest stay real SIGKILLs.  Stops translate to nothing — they
    are parent-side (the spawner owns the SIGSTOP timers)."""
    from repro.launch.elastic import DEPART_ENV, KILL_ENV

    env = {}
    parked = dict(chaos["departs"])
    real_kills = []
    for r, k, at_barrier in chaos["kills"]:
        if r in chaos["rejoins"] and not at_barrier:
            parked[r] = k
        else:
            real_kills.append((r, k, at_barrier))
    if len(parked) > 1:
        raise SystemExit(f"--chaos: at most one depart/rejoin pair per "
                         f"run (got ranks {sorted(parked)})")
    if real_kills:
        env[KILL_ENV] = ",".join(
            f"{r}:{k}" + (":barrier" if b else "")
            for r, k, b in real_kills)
    for r, k in parked.items():
        env[DEPART_ENV] = f"{r}:{k}:{chaos['rejoins'][r]}"
    return env


def _chaos_real_kills(chaos: dict) -> list:
    return [(r, k, b) for r, k, b in chaos["kills"]
            if b or r not in chaos["rejoins"]]


def _build_demo_store(workdir: Path, p: int, *, n: int = 256, d: int = 32,
                      density: float = 0.3, seed: int = 0,
                      codec: str | None = None, timeout: float = 120.0):
    """Rank 0 ingests the fixture; other ranks wait for the manifest."""
    import numpy as np
    import jax

    from repro.data.sparse import dense_to_csr
    from repro.data.synthetic import make_sparse_classification
    from repro.datasets.libsvm import write_libsvm
    from repro.datasets.shards import MANIFEST, ingest_libsvm, open_store

    shards = workdir / "demo-shards"
    if jax.process_index() == 0:
        X, y, _ = make_sparse_classification(n, d, density=density,
                                             seed=seed)
        csr = dense_to_csr(np.asarray(X))
        svm = workdir / "demo.svm"
        write_libsvm(svm, np.asarray(csr.vals), np.asarray(csr.cols),
                     np.asarray(csr.row_nnz), np.asarray(y))
        return ingest_libsvm(svm, shards, p=p, n_features=d, codec=codec)
    deadline = time.monotonic() + timeout
    while not (shards / MANIFEST).exists():
        if time.monotonic() > deadline:
            raise TimeoutError(f"rank {jax.process_index()}: no demo store "
                               f"manifest at {shards} after {timeout}s")
        time.sleep(0.05)
    return open_store(shards)


def _run_rank(args) -> int:
    from repro.launch.mesh import MeshSpec, init_distributed, run_mesh

    info = init_distributed(args.coordinator, args.num_processes,
                            args.process_id, elastic=args.elastic,
                            external_service=(True if args.external_service
                                              else None))
    import jax
    import numpy as np

    from repro.core import LOGISTIC, PScopeConfig, Regularizer
    from repro.datasets.shards import open_store

    if args.store:
        store = open_store(args.store)
    elif args.demo:
        workdir = Path(args.workdir or
                       os.environ.get("REPRO_MULTIHOST_WORKDIR", "."))
        workdir.mkdir(parents=True, exist_ok=True)
        store = _build_demo_store(workdir, p=jax.device_count(),
                                  seed=args.seed, codec=args.codec)
    else:
        raise SystemExit("need --store DIR or --demo")

    reg = Regularizer(args.lam1, args.lam2)
    cfg = PScopeConfig(eta=args.eta, inner_steps=args.inner_steps,
                       inner_batch=args.inner_batch,
                       outer_steps=args.rounds, seed=args.seed,
                       inner_path=args.inner_path)
    if args.elastic:
        from repro.launch.elastic import (DEPART_ENV, ElasticConfig,
                                          KILL_ENV, run_mesh_elastic)
        if args.kill_rank is not None:
            if args.kill_at_round >= args.rounds:
                raise SystemExit(
                    f"--kill-at-round {args.kill_at_round} is past the "
                    f"{args.rounds}-round schedule: nothing would die")
            if args.rejoin is not None:
                if not args.kill_at_round < args.rejoin < args.rounds:
                    raise SystemExit(
                        f"--rejoin {args.rejoin} must land strictly "
                        f"between --kill-at-round ({args.kill_at_round}) "
                        f"and --rounds ({args.rounds})")
                os.environ[DEPART_ENV] = (f"{args.kill_rank}:"
                                          f"{args.kill_at_round}:"
                                          f"{args.rejoin}")
            else:
                os.environ[KILL_ENV] = (
                    f"{args.kill_rank}:{args.kill_at_round}")
        ecfg = ElasticConfig(check_every=args.check_every,
                             heartbeat_interval_s=args.hb_interval,
                             heartbeat_timeout_s=args.hb_timeout,
                             marker_timeout_s=args.marker_timeout,
                             checkpoint_dir=args.ckpt_dir,
                             control=args.control or "kv")
        res = run_mesh_elastic(LOGISTIC, reg, store, None,
                               np.zeros(store.d, np.float32), cfg,
                               ecfg=ecfg)
    else:
        spec = MeshSpec.for_workers(store.p)
        res = run_mesh(LOGISTIC, reg, store, None,
                       np.zeros(store.d, np.float32), cfg, spec)

    payload = {
        "process_id": res.process_id, "num_processes": res.num_processes,
        "local_worker_ids": list(res.worker_ids),
        "values": res.values.tolist(), "nnz": res.nnz.tolist(),
        "comm_bytes_per_round": res.comm_bytes_per_round,
        "seconds": res.seconds,
    }
    if args.elastic:
        payload["events"] = list(res.events)
        payload["epoch"] = res.epoch
        payload["survivors"] = list(res.survivors)
        payload["rejoined"] = bool(res.rejoined)
        payload["remesh_overlap_saved_s"] = res.remesh_overlap_saved_s
    print("RESULT " + json.dumps(payload), flush=True)

    if args.trace_out:
        # per-rank spool, merged by the spawner (or by hand with
        # obs.merge_spools) into one clock-aligned Chrome trace.  Must
        # happen before the degraded-path exit_now below — a hard exit
        # never flushes.
        from repro import obs
        obs.write_spool(obs.spool_path(args.trace_out, info["process_id"]))

    rc = 0
    if info["process_id"] == args.verify_rank:
        if args.out:
            Path(args.out).write_text(json.dumps(payload, indent=2) + "\n")
        if args.events_out and args.elastic:
            with open(args.events_out, "w") as fh:
                for ev in res.events:
                    fh.write(json.dumps(dict(ev, rank=res.process_id))
                             + "\n")
        if args.verify:
            from repro.core.pscope import run_scanned
            _, v_ref, nnz_ref = run_scanned(
                LOGISTIC, reg, store.csr_p, np.asarray(store.yp),
                np.zeros(store.d, np.float32), cfg)
            diff = float(np.max(np.abs(res.values - v_ref)))
            ok = (np.allclose(res.values, v_ref, rtol=1e-5, atol=1e-5)
                  and np.array_equal(res.nnz, nnz_ref))
            print(f"VERIFY {'OK' if ok else 'FAIL'} max|dv|={diff:.3g}",
                  flush=True)
            if not ok:
                rc = 1
    if args.elastic and getattr(res, "degraded", False):
        # a rank died this run: the jax.distributed shutdown barrier
        # would wait forever for it — hard-exit past it.  Rank 0 hosts
        # the coordination service (unless it is external), so it
        # lingers: exiting first would close the service socket and
        # terminate followers that haven't flushed their RESULT line.
        from repro.launch.elastic import exit_now
        if res.process_id == 0 and not args.external_service:
            time.sleep(2.0)
        exit_now(rc)
    return rc


def _spawn(args) -> int:
    """Fork N local ranks of this module, timeout-guarded; runs the
    chaos schedule's parent-side events (SIGSTOP timers, the external
    service host) and validates the surviving traces."""
    port = _free_port()
    n = args.spawn
    workdir = args.workdir or f".multihost-demo-{port}"

    chaos = parse_chaos(args.chaos) if args.chaos else None
    extra_env = {}
    real_kills = []
    rejoin_ranks: set = set()
    if chaos is not None:
        args.elastic = True
        validate_chaos(chaos, num_processes=n, rounds=args.rounds,
                       hb_timeout=args.hb_timeout)
        extra_env = chaos_env(chaos)
        real_kills = _chaos_real_kills(chaos)
        rejoin_ranks = set(chaos["rejoins"]) - set(
            r for r, _, _ in real_kills)
        if args.control is None:
            # fault schedules need verdicts that outlive any rank
            args.control = f"file:{os.path.join(workdir, 'control')}"
    killed_ranks = set(r for r, _, _ in real_kills)
    if args.elastic and args.kill_rank is not None \
            and args.rejoin is None:
        killed_ranks.add(args.kill_rank)
    coordinator_killed = 0 in killed_ranks
    # chaos always hosts the service OUTSIDE the ranks: rank 0 may die
    # for real (the service must outlive it), and even a surviving
    # rank 0 exits on its own schedule — an in-rank service closing
    # while a slower rank still polls it is a spurious QFATAL
    external_service = bool(args.external_service or chaos is not None)
    verify_rank = min(set(range(n)) - killed_ranks - rejoin_ranks)

    argv = [sys.executable, "-m", "repro.launch.multihost",
            "--coordinator", f"127.0.0.1:{port}",
            "--num-processes", str(n)]
    passthrough = ["--rounds", str(args.rounds), "--eta", str(args.eta),
                   "--inner-steps", str(args.inner_steps),
                   "--inner-batch", str(args.inner_batch),
                   "--lam1", str(args.lam1), "--lam2", str(args.lam2),
                   "--seed", str(args.seed),
                   "--inner-path", args.inner_path,
                   "--workdir", workdir,
                   "--verify-rank", str(verify_rank)]
    if args.store:
        passthrough += ["--store", args.store]
    else:
        passthrough += ["--demo"]
        if args.codec:
            passthrough += ["--codec", args.codec]
    if args.verify:
        passthrough += ["--verify"]
    if args.out:
        passthrough += ["--out", args.out]
    if args.trace_out:
        passthrough += ["--trace-out", args.trace_out]
    if args.events_out:
        passthrough += ["--events-out", args.events_out]
    if external_service:
        passthrough += ["--external-service"]
    if args.elastic:
        passthrough += ["--elastic", "--check-every", str(args.check_every),
                        "--hb-interval", str(args.hb_interval),
                        "--hb-timeout", str(args.hb_timeout),
                        "--marker-timeout", str(args.marker_timeout)]
        if args.control:
            passthrough += ["--control", args.control]
        if args.ckpt_dir:
            passthrough += ["--ckpt-dir", args.ckpt_dir]
        if args.kill_rank is not None:
            passthrough += ["--kill-rank", str(args.kill_rank),
                            "--kill-at-round", str(args.kill_at_round)]
            if args.rejoin is not None:
                passthrough += ["--rejoin", str(args.rejoin)]

    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.update(extra_env)
    if args.devices_per_process > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count="
                            f"{args.devices_per_process}").strip()

    service = None
    if external_service:
        service = subprocess.Popen(
            [sys.executable, "-m", "repro.launch.multihost",
             "--service-host", "--coordinator", f"127.0.0.1:{port}",
             "--num-processes", str(n)],
            env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        if "SERVICE-HOST UP" not in (service.stdout.readline() or ""):
            service.kill()
            print("external service host failed to come up",
                  file=sys.stderr)
            return 1
        env["REPRO_SERVICE_EXTERNAL"] = "1"

    procs = [subprocess.Popen(argv + passthrough + ["--process-id", str(r)],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
             for r in range(n)]

    stop_timers = []
    if chaos is not None:
        def _sig(r, signum):
            if procs[r].poll() is None:
                procs[r].send_signal(signum)
        for r, at_s, for_s in chaos["stops"]:
            t1 = threading.Timer(at_s, _sig, (r, signal.SIGSTOP))
            t2 = threading.Timer(at_s + for_s, _sig, (r, signal.SIGCONT))
            t1.start(), t2.start()
            stop_timers += [t1, t2]

    deadline = time.monotonic() + args.timeout
    outs = [None] * n
    try:
        for r, proc in enumerate(procs):
            left = deadline - time.monotonic()
            if left <= 0:
                raise subprocess.TimeoutExpired(argv, args.timeout)
            outs[r], _ = proc.communicate(timeout=left)
    except subprocess.TimeoutExpired:
        for proc in procs:
            proc.send_signal(signal.SIGCONT)   # un-stop before the kill
            proc.kill()
        print(f"TIMEOUT after {args.timeout}s (hung collective?); "
              "killed all ranks", file=sys.stderr)
        return 2
    finally:
        for t in stop_timers:
            t.cancel()
        if service is not None:
            service.kill()
            service.communicate()

    legacy_victim = (args.kill_rank
                     if (args.elastic and args.kill_rank is not None
                         and args.rejoin is None and chaos is None)
                     else None)
    if legacy_victim is not None:
        killed_ranks = {legacy_victim}
    results = {}
    for r, (proc, out) in enumerate(zip(procs, outs)):
        sys.stdout.write(out or "")
        if r in killed_ranks:
            continue   # SIGKILLed mid-run by design: no exit code contract
        if proc.returncode != 0:
            print(f"rank {r} exited {proc.returncode}", file=sys.stderr)
            return proc.returncode or 1
        lines = [ln for ln in (out or "").splitlines()
                 if ln.startswith("RESULT ")]
        if not lines:
            print(f"rank {r} produced no RESULT line", file=sys.stderr)
            return 1
        results[r] = json.loads(lines[-1][len("RESULT "):])

    if args.trace_out:
        # killed ranks never wrote a spool (SIGKILL flushes nothing);
        # merge_spools skips what it can't read
        from repro import obs
        try:
            obs.merge_spools(f"{args.trace_out}.rank*.spool.json",
                             out=args.trace_out)
            print(f"TRACE OK: merged timeline -> {args.trace_out}")
        except ValueError as exc:
            print(f"TRACE WARN: {exc}", file=sys.stderr)

    full = {r: res for r, res in results.items() if r not in rejoin_ranks}
    vals = [tuple(res["values"]) for res in full.values()]
    if len(set(vals)) != 1:
        print("FAIL: ranks returned divergent traces", file=sys.stderr)
        return 1
    ref = vals[0]
    for r in sorted(rejoin_ranks):
        suffix = tuple(results[r]["values"])
        tail = ref[len(ref) - len(suffix):]
        # the suffix's FIRST value (the objective at the resume round)
        # is recomputed on the rejoined mesh, so it matches the
        # survivors' pre-rejoin-mesh value only to fp32 reassociation;
        # everything after runs on the identical mesh and is exact
        import math
        ok = (0 < len(suffix) < len(ref)
              and all(math.isclose(a, b, rel_tol=1e-5, abs_tol=1e-5)
                      for a, b in zip(suffix, tail))
              and suffix[1:] == tail[1:])
        if not ok:
            print(f"FAIL: rejoined rank {r}'s trace is not a suffix of "
                  f"the survivors' trace", file=sys.stderr)
            return 1
        if not results[r]["local_worker_ids"]:
            print(f"FAIL: rejoined rank {r} ended the run owning no "
                  f"workers", file=sys.stderr)
            return 1
        print(f"REJOIN OK: rank {r} re-admitted, trace suffix of "
              f"{len(suffix)}/{len(ref)} rounds, owns workers "
              f"{results[r]['local_worker_ids']}")

    if legacy_victim is not None:
        events = next(iter(full.values())).get("events", [])
        if not events or events[-1]["dead"] != [legacy_victim]:
            print(f"FAIL: survivors recorded no re-mesh naming rank "
                  f"{legacy_victim}: {events}", file=sys.stderr)
            return 1
        ev = events[-1]
        print(f"ELASTIC OK: rank {legacy_victim} killed at round "
              f"{ev['round']}, {len(full)} survivors re-meshed in "
              f"{ev['remesh_seconds']:.2f}s, resumed at round "
              f"{ev['resume_round']}")
    elif chaos is not None:
        events = next(iter(full.values())).get("events", [])
        dead_seen = sorted(set(r for ev in events for r in ev["dead"]))
        want_dead = sorted(set(r for r, _, _ in chaos["kills"])
                           | set(chaos["departs"]))
        if dead_seen != want_dead:
            print(f"FAIL: schedule killed/departed {want_dead} but the "
                  f"survivors' events name {dead_seen}: {events}",
                  file=sys.stderr)
            return 1
        if coordinator_killed:
            print("CHAOS OK: coordinator (rank 0) died; survivors "
                  "promoted a new verdict issuer and finished")
        if want_dead or chaos["stops"]:
            print(f"CHAOS OK: schedule {args.chaos!r} survived "
                  f"({len(events)} re-mesh events)")
    print(f"SPAWN OK: {len(full)} ranks, bit-identical traces, "
          f"{next(iter(full.values()))['comm_bytes_per_round']:.0f} "
          f"comm bytes/round")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.multihost",
        description="multi-host CALL mesh launcher")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--spawn", type=int, default=None, metavar="N",
                    help="single-node mode: fork N ranks wired to a fresh "
                         "coordinator port")
    ap.add_argument("--service-host", action="store_true",
                    help="host ONLY the coordination service (never "
                         "joins the mesh): makes rank-0 loss survivable "
                         "on the kv control plane")
    ap.add_argument("--external-service", action="store_true",
                    help="the coordination service runs in a separate "
                         "--service-host process; every rank (0 "
                         "included) connects as a plain client")
    ap.add_argument("--devices-per-process", type=int, default=1,
                    help="(--spawn) forced host devices per rank")
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="(--spawn) kill the job after this many seconds")
    ap.add_argument("--store", default=None, metavar="DIR",
                    help="committed ShardStore directory (shared FS)")
    ap.add_argument("--demo", action="store_true",
                    help="rank 0 ingests a small synthetic fixture store")
    ap.add_argument("--workdir", default=None,
                    help="where --demo writes its fixture store")
    ap.add_argument("--codec", default=None, metavar="NAME",
                    help="(--demo) ingest the fixture store with this "
                         "segment codec (e.g. delta+bf16); every rank "
                         "then maps compressed extents and the mesh "
                         "solver decodes values in-kernel")
    ap.add_argument("--verify", action="store_true",
                    help="check the mesh trace against the "
                         "single-process run_scanned reference")
    ap.add_argument("--verify-rank", type=int, default=0,
                    help="which rank runs --verify/--out (the spawner "
                         "picks the lowest rank the chaos schedule "
                         "leaves alive)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="the verify rank writes the trace JSON here")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="telemetry: each rank spools its spans/counters "
                         "to PATH.rankN.spool.json; the spawner merges "
                         "them into one Chrome-trace JSON at PATH "
                         "(load in Perfetto / chrome://tracing)")
    ap.add_argument("--events-out", default=None, metavar="PATH",
                    help="(--elastic) the verify rank writes the re-mesh "
                         "event log as JSON Lines, one event per line")
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--eta", type=float, default=0.5)
    ap.add_argument("--inner-steps", type=int, default=64)
    ap.add_argument("--inner-batch", type=int, default=2)
    ap.add_argument("--lam1", type=float, default=1e-3)
    ap.add_argument("--lam2", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--inner-path", default="lazy",
                    choices=("dense", "lazy", "auto"))
    ap.add_argument("--elastic", action="store_true",
                    help="chunked elastic driver: survives rank deaths "
                         "by re-meshing the survivors (see "
                         "docs/multihost.md)")
    ap.add_argument("--check-every", type=int, default=2,
                    help="(--elastic) rounds per failure-detection chunk")
    ap.add_argument("--hb-interval", type=float, default=0.25,
                    help="(--elastic) heartbeat publish period, seconds")
    ap.add_argument("--hb-timeout", type=float, default=4.0,
                    help="(--elastic) stale-heartbeat death threshold")
    ap.add_argument("--marker-timeout", type=float, default=6.0,
                    help="(--elastic) chunk-marker wait before the "
                         "leader consults heartbeats")
    ap.add_argument("--control", default=None, metavar="SPEC",
                    help="(--elastic) control-plane backend: kv | "
                         "file:DIR | local (--chaos defaults to a "
                         "file: plane under --workdir)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="(--elastic) cold-fallback checkpoint directory")
    ap.add_argument("--kill-rank", type=int, default=None,
                    help="(--elastic) fault injection: this rank "
                         "SIGKILLs itself mid-run")
    ap.add_argument("--kill-at-round", type=int, default=3,
                    help="(--elastic) round after which --kill-rank dies")
    ap.add_argument("--rejoin", type=int, default=None, metavar="ROUND",
                    help="(--elastic, with --kill-rank) the killed rank "
                         "parks instead of exiting and rejoins at this "
                         "round (park/revive simulation)")
    ap.add_argument("--chaos", default=None, metavar="SCHEDULE",
                    help="(--spawn) declarative fault schedule, e.g. "
                         "'kill-coordinator@2,rejoin@4' or "
                         "'kill:1@2,kill:2@4' (implies --elastic; see "
                         "docs/multihost.md)")
    args = ap.parse_args(argv)

    if args.service_host:
        if not args.coordinator or args.num_processes is None:
            raise SystemExit("--service-host needs --coordinator "
                             "HOST:PORT and --num-processes")
        from repro.launch.control import run_service_host
        run_service_host(args.coordinator, args.num_processes)
        return 0
    if args.chaos is not None and args.spawn is None:
        raise SystemExit("--chaos is a --spawn option (the spawner owns "
                         "the schedule's parent-side events)")
    if args.spawn is not None:
        return _spawn(args)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    return _run_rank(args)


if __name__ == "__main__":
    sys.exit(main())
