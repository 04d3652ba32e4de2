"""Externalized control plane for elastic multi-host CALL.

PR 7's recovery protocol (heartbeats, chunk done-markers, the leader's
verdicts, the re-mesh barrier) spoke directly to the `jax.distributed`
coordination-service KV store — which lives inside rank 0's process, so
losing the coordinator lost the control plane with it and forced the
cold checkpoint fallback.  This module factors the store behind a
`ControlPlane` interface with three backends:

  * `LocalControlPlane` — in-process dict (single-process runs and
    protocol unit tests; PR 7's `LocalKV`).
  * `DistributedKVControlPlane` — the coordination-service KV of the
    running `jax.distributed` job.  Survives a coordinator-rank death
    ONLY when the service itself is hosted outside the ranks (see
    "external service host" below).
  * `FileControlPlane` — a directory on a filesystem every rank can
    reach (NFS, or a local path for single-node spawns).  Every key is
    a file committed by atomic rename, `try_claim` is a first-write-
    wins exclusive link, and `list` is a directory walk.  No process
    hosts anything: the control plane survives ANY rank's death,
    including rank 0's.

Fencing.  Leadership (who issues verdicts) is "the lowest-ranked
survivor"; when the leader dies, the next rank promotes itself.  Two
mechanisms prevent a zombie ex-leader (paused, declared dead, resumed)
from split-braining the run:

  1. every verdict is published with `try_claim` — first write wins,
     atomically; late writers read back the winning verdict and obey
     it like any follower;
  2. each promotion claims a **fencing generation**
     (`{ns}/fence/g{G}`): a leader re-checks that it still holds the
     newest generation immediately before claiming a verdict, and
     abdicates if it was fenced out.

The jax coordination *service* (which gloo also uses for communicator
rendezvous) can be hosted by a standalone process so that no mesh rank
is load-bearing: `run_service_host` below, wired to
``python -m repro.launch.multihost --service-host`` and the
``--external-service`` spawn flag.  See docs/multihost.md.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, Optional

from repro.train.checkpoint import atomic_write_text

#: env var marking that the coordination service is hosted OUTSIDE the
#: mesh ranks (a `--service-host` process): rank 0 then brings up a
#: client only, and its death no longer tears the service down.
SERVICE_EXTERNAL_ENV = "REPRO_SERVICE_EXTERNAL"


def service_is_external() -> bool:
    return bool(int(os.environ.get(SERVICE_EXTERNAL_ENV, "0")))


# ---------------------------------------------------------------------------
# The interface
# ---------------------------------------------------------------------------

class ControlPlane:
    """String KV store with prefix listing and first-write-wins claims.

    Keys are '/'-separated paths.  The elastic protocol only ever lists
    directory-style prefixes (trailing '/'), which every backend
    supports; exact-key reads go through `list` of the parent prefix.
    """

    #: True when the backend outlives the death of ANY single rank —
    #: including the leader / rank 0.  Gates leader promotion: with a
    #: coordinator-hosted backend there is nothing left to promote onto.
    survives_coordinator: bool = False

    def set(self, key: str, value: str) -> None:
        raise NotImplementedError

    def try_claim(self, key: str, value: str) -> str:
        """Atomically publish `value` under `key` unless a value is
        already there; returns the WINNING value either way (first
        write wins — the fencing primitive)."""
        raise NotImplementedError

    def list(self, prefix: str) -> Dict[str, str]:
        raise NotImplementedError

    def delete(self, key: str) -> None:
        """Best-effort removal (protocol hygiene, never correctness)."""

    def close(self) -> None:
        """Release backend resources (no-op for most)."""


class LocalControlPlane(ControlPlane):
    """Dict-backed stand-in (single-process runs and protocol tests)."""

    survives_coordinator = True      # nothing to lose: it IS the process

    def __init__(self):
        self._d: Dict[str, str] = {}
        self._lock = threading.Lock()

    def set(self, key: str, value: str) -> None:
        with self._lock:
            self._d[key] = value

    def try_claim(self, key: str, value: str) -> str:
        with self._lock:
            return self._d.setdefault(key, value)

    def list(self, prefix: str) -> Dict[str, str]:
        with self._lock:
            return {k: v for k, v in self._d.items()
                    if k.startswith(prefix)}

    def delete(self, key: str) -> None:
        with self._lock:
            self._d.pop(key, None)


class DistributedKVControlPlane(ControlPlane):
    """The coordination-service KV store of the running
    `jax.distributed` job.  Writes are visible to every live process; a
    dead process's keys persist (its heartbeat counter simply stops
    advancing — which is exactly the liveness signal).

    The store lives wherever the coordination service runs: inside
    rank 0 under the classic bring-up (coordinator loss loses the
    store), or inside a standalone `--service-host` process (coordinator
    loss is then survivable — `survives_coordinator` reflects which)."""

    def __init__(self):
        from jax._src import distributed as _dist
        client = getattr(_dist.global_state, "client", None)
        if client is None:
            raise RuntimeError("DistributedKVControlPlane needs an "
                               "initialized jax.distributed job "
                               "(init_distributed)")
        self._client = client
        self.survives_coordinator = service_is_external()

    def set(self, key: str, value: str) -> None:
        self._client.key_value_set(key, value, allow_overwrite=True)

    def try_claim(self, key: str, value: str) -> str:
        try:
            self._client.key_value_set(key, value, allow_overwrite=False)
            return value
        except Exception:            # noqa: BLE001 — lost the race:
            pass                     # read back the winner below
        deadline = time.monotonic() + 10.0
        prefix = key.rsplit("/", 1)[0] + "/"
        while time.monotonic() < deadline:
            got = self.list(prefix).get(key)
            if got is not None:
                return got
            time.sleep(0.01)
        raise RuntimeError(f"try_claim({key!r}): claim failed but no "
                           f"winning value appeared")

    def list(self, prefix: str) -> Dict[str, str]:
        return {k: v for k, v in self._client.key_value_dir_get(prefix)}

    def delete(self, key: str) -> None:
        try:
            self._client.key_value_delete(key)
        except Exception:            # noqa: BLE001 — hygiene only
            pass


class FileControlPlane(ControlPlane):
    """Directory-backed control plane (NFS or local filesystem).

    Layout: key ``a/b/c`` is the file ``<root>/a/b/c``.  Commit
    discipline:

      * `set` writes to a same-directory temp file and `os.rename`s it
        over the key — readers only ever see complete values (rename is
        atomic on POSIX filesystems, including NFS);
      * `try_claim` writes the temp file then `os.link`s it to the key:
        link fails with EEXIST if any writer got there first, so the
        first claim wins atomically even across hosts — the primitive
        the verdict/fencing protocol is built on;
      * `list` walks the prefix directory (the protocol's prefixes are
        small: one file per rank per chunk).

    Values are capped only by the filesystem; the elastic layer ships
    the replicated iterate through here on re-admission (base64, d
    floats), which a KV RPC limit could reject but a file cannot.
    """

    survives_coordinator = True

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        os.makedirs(self.root, exist_ok=True)

    def _path(self, key: str) -> str:
        parts = [p for p in key.split("/") if p not in ("", ".", "..")]
        if not parts:
            raise ValueError(f"bad control-plane key {key!r}")
        return os.path.join(self.root, *parts)

    def set(self, key: str, value: str) -> None:
        atomic_write_text(self._path(key), value)

    def try_claim(self, key: str, value: str) -> str:
        path = self._path(key)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.claim.{os.getpid()}.{threading.get_ident()}"
        with open(tmp, "w") as f:
            f.write(value)
            f.flush()
            os.fsync(f.fileno())
        try:
            os.link(tmp, path)       # atomic, fails if claimed already
            return value
        except FileExistsError:
            # lost the race; the winner's rename/link already landed,
            # but its value may still be mid-flight on a remote NFS
            # attribute cache — retry the read briefly
            deadline = time.monotonic() + 10.0
            while True:
                try:
                    with open(path, "r") as f:
                        return f.read()
                except OSError:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.01)
        finally:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def list(self, prefix: str) -> Dict[str, str]:
        parts = [p for p in prefix.split("/") if p not in ("", ".", "..")]
        base = os.path.join(self.root, *parts) if parts else self.root
        # a non-directory prefix ("ns/hb/" vs file "ns/hb") lists empty
        if not os.path.isdir(base):
            return {}
        out: Dict[str, str] = {}
        rel0 = prefix if prefix.endswith("/") else prefix + "/"
        for dirpath, _, names in os.walk(base):
            for name in names:
                if ".claim." in name or name.endswith(".tmp"):
                    continue         # in-flight writes are invisible
                path = os.path.join(dirpath, name)
                rel = os.path.relpath(path, base).replace(os.sep, "/")
                try:
                    with open(path, "r") as f:
                        out[rel0 + rel] = f.read()
                except OSError:
                    continue         # concurrently replaced — next poll
        return out

    def delete(self, key: str) -> None:
        try:
            os.unlink(self._path(key))
        except OSError:
            pass


def validate_control_spec(spec: Optional[str]) -> None:
    """Reject a malformed control-plane spec at CONFIG time (before a
    run is mid-flight) — same grammar as `make_control_plane`."""
    if spec in (None, "kv", "local"):
        return
    if isinstance(spec, str) and spec.startswith("file:") and \
            spec[len("file:"):]:
        return
    raise ValueError(f"unknown control-plane spec {spec!r} "
                     f"(expected 'kv', 'local', or 'file:<path>')")


def make_control_plane(spec: Optional[str], num_processes: int
                       ) -> ControlPlane:
    """Resolve a control-plane spec string to a backend.

        None / "kv"    coordination-service KV (LocalControlPlane when
                       the job is single-process)
        "local"        in-process dict
        "file:<path>"  FileControlPlane rooted at <path>
    """
    if spec in (None, "kv"):
        if num_processes <= 1:
            return LocalControlPlane()
        return DistributedKVControlPlane()
    if spec == "local":
        return LocalControlPlane()
    if spec.startswith("file:"):
        path = spec[len("file:"):]
        if not path:
            raise ValueError("control spec 'file:' needs a path "
                             "(file:/shared/run-control)")
        return FileControlPlane(path)
    raise ValueError(f"unknown control-plane spec {spec!r} "
                     f"(expected 'kv', 'local', or 'file:<path>')")


# ---------------------------------------------------------------------------
# Fencing generations
# ---------------------------------------------------------------------------

def fence_key(ns: str, generation: int) -> str:
    return f"{ns}/fence/g{generation}"


def claim_fence(plane: ControlPlane, ns: str, generation: int,
                rank: int) -> int:
    """Claim leadership generation `generation`; returns the rank that
    actually holds it (first claimer wins)."""
    return int(plane.try_claim(fence_key(ns, generation), str(int(rank))))


def newest_fence(plane: ControlPlane, ns: str) -> tuple[int, Optional[int]]:
    """(newest claimed generation, its holder rank); (-1, None) when no
    generation was ever claimed."""
    best, holder = -1, None
    for key, val in plane.list(f"{ns}/fence/").items():
        tail = key.rsplit("/", 1)[-1]
        if not tail.startswith("g"):
            continue
        try:
            g, r = int(tail[1:]), int(val)
        except ValueError:
            continue
        if g > best:
            best, holder = g, r
    return best, holder


# ---------------------------------------------------------------------------
# Standalone coordination-service host
# ---------------------------------------------------------------------------

def run_service_host(bind_address: str, num_processes: int, *,
                     heartbeat_timeout_s: int = 86400,
                     ready_event: Optional[threading.Event] = None,
                     stop_event: Optional[threading.Event] = None) -> None:
    """Host the `jax.distributed` coordination service in THIS process,
    which never joins the mesh: rank deaths (rank 0 included) cannot
    close the service socket, so survivor KV traffic and gloo
    communicator rendezvous keep working through any single failure.

    `heartbeat_timeout_s` defaults high for the same reason as
    `init_distributed(elastic=True)`: the service must not declare a
    silently-dead task failed (and push a fatal error to every polling
    client) while the elastic layer is busy recovering from it.

    Blocks until `stop_event` (or forever); `ready_event` is set once
    the service is listening — callers forking this as a child can wait
    on the ``SERVICE-HOST UP`` stdout line instead.
    """
    from jax._src.lib import _jax

    if ":" not in bind_address:
        raise ValueError(f"bind address must be host:port, got "
                         f"{bind_address!r}")
    bind = "[::]:" + bind_address.rsplit(":", 1)[1]
    service = _jax.get_distributed_runtime_service(
        bind, num_processes, heartbeat_timeout=int(heartbeat_timeout_s))
    print(f"SERVICE-HOST UP {bind_address} ({num_processes} ranks)",
          flush=True)
    if ready_event is not None:
        ready_event.set()
    try:
        if stop_event is not None:
            stop_event.wait()
        else:
            while True:
                time.sleep(3600)
    finally:
        service.shutdown()


def join_request_key(ns: str, rank: int) -> str:
    return f"{ns}/join/{rank}"


def progress_key(ns: str) -> str:
    # directory-style: the coordination-service KV only lists keys
    # strictly UNDER a prefix, so the value lives at ".../p"
    return f"{ns}/progress/p"


def publish_progress(plane: ControlPlane, ns: str, *, round_: int,
                     epoch: int, chunk: int, survivors, ownership,
                     leader: int, fence_generation: int) -> None:
    """The leader's per-chunk run-state beacon: everything a departed
    or late-joining rank needs to find the run again (current round,
    mesh epoch, membership, ownership, who leads under which fence)."""
    plane.set(progress_key(ns), json.dumps({
        "round": int(round_), "epoch": int(epoch), "chunk": int(chunk),
        "survivors": [int(r) for r in survivors],
        "ownership": {int(r): [int(w) for w in ws]
                      for r, ws in ownership.items()},
        "leader": int(leader), "fence_generation": int(fence_generation),
    }))


def read_progress(plane: ControlPlane, ns: str) -> Optional[dict]:
    raw = plane.list(f"{ns}/progress/").get(progress_key(ns))
    if raw is None:
        return None
    out = json.loads(raw)
    out["ownership"] = {int(r): tuple(ws)
                        for r, ws in out["ownership"].items()}
    return out
