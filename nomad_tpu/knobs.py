"""Single declared registry for every ``NOMAD_TPU_*`` tuning knob.

Every environment variable the runtime consults is declared here —
name, default, type, one-line doc — and read through the typed
accessors (`get_str` / `get_int` / `get_float` / `get_bool`).  The
`knob-registry` static checker (`nomad_tpu/analysis/knob_registry.py`)
enforces the contract from the other side: a raw ``os.environ`` /
``getenv`` read of a ``NOMAD_TPU_*`` literal anywhere outside this file
is a finding, as is a registered knob nothing reads (dead entry) or one
missing from the README knob table (doc drift).

Accessors hit ``os.environ`` at *call* time — nothing is cached — so
tests can monkeypatch the environment and `override()` can scope a
value to a block.  An empty string counts as unset (several knobs use
"" for "auto"); the ``default=`` parameter lets a call site supply a
dynamic fallback (e.g. ``NOMAD_TPU_WAVE`` defaulting to the scheduler
count) that overrides the registry default.

Regenerate the README table with ``python -m nomad_tpu.knobs``.
"""
from __future__ import annotations

import contextlib
import os
from typing import Dict, Iterator, Mapping, Optional

# Marker the knob-registry checker keys on to find this file in a
# corpus (fixture corpora declare their own registry module the same
# way).
_KNOB_REGISTRY = True


class Knob:
    """One registered knob: wire default (string form, "" = unset/auto),
    type name ("str" | "int" | "float" | "bool"), one-line doc."""

    __slots__ = ("default", "type", "doc")

    def __init__(self, default: str, type: str, doc: str) -> None:
        self.default = default
        self.type = type
        self.doc = doc


# The registry is a plain dict literal of Knob(...) calls with constant
# arguments so the static checker can read it without importing us.
KNOBS: Dict[str, Knob] = {
    # -- parallel engine / serving mesh ------------------------------
    "NOMAD_TPU_SHARD_MIN": Knob(
        "128", "int",
        "minimum padded node rows before dispatches route over the "
        "`('node_shard','wave')` mesh (`shard_min_nodes`); above the "
        "cluster's size it keeps a multi-device host on one device"),
    "NOMAD_TPU_WAVE_SHARDS": Knob(
        "", "int",
        "wave extent of the 2-D serving mesh (`wave_mesh_shape`); "
        "empty = auto, a non-divisor of the device count falls back "
        "to 1"),
    "NOMAD_TPU_BULK_BYTES": Knob(
        "268435456", "int",
        "byte budget for one bulk dispatch's stacked per-eval "
        "tensors; caps the eval-axis chain length at large N"),
    "NOMAD_TPU_WARM_THREADS": Knob(
        "4", "int",
        "parallelism of `engine.warmup` kernel-variant compilation"),
    "NOMAD_TPU_PLAN_BATCH": Knob(
        "64", "int",
        "plan applier batch size (commit coalescing; sized to swallow "
        "a full feeder wave per raft apply)"),
    "NOMAD_TPU_PIPELINE_DEPTH": Knob(
        "2", "int",
        "in-flight commit waves a worker may run ahead of "
        "(double-buffer depth); `0` restores blocking submit"),
    "NOMAD_TPU_WAVE": Knob(
        "", "int",
        "max evals the `EvalWaveFeeder` drains per broker pass "
        "(empty = the server's scheduler count)"),
    # -- autopilot ---------------------------------------------------
    "NOMAD_TPU_AUTOPILOT_INTERVAL": Knob(
        "0.05", "float",
        "autopilot tick interval (leader-side server-lifecycle loop)"),
    "NOMAD_TPU_AUTOPILOT_STABILIZATION": Knob(
        "0.25", "float",
        "how long a non-voter must stay healthy before promotion to "
        "voter"),
    "NOMAD_TPU_AUTOPILOT_LAG": Knob(
        "16", "int",
        "max log entries a server may trail the leader and still "
        "count as healthy"),
    "NOMAD_TPU_AUTOPILOT_REAP_AFTER": Knob(
        "1.0", "float",
        "seconds a gossip-FAILED server stays in the raft config "
        "before autopilot removes it"),
    # -- raft / fleet plumbing ---------------------------------------
    "NOMAD_TPU_FSYNC": Knob(
        "batch", "str",
        "WAL fsync policy: `always` | `batch` | `off`"),
    "NOMAD_TPU_SNAP_CHUNK": Knob(
        "262144", "int",
        "frame size (bytes) of the chunked InstallSnapshot stream"),
    "NOMAD_TPU_SNAP_WINDOW": Knob(
        "8", "int",
        "snapshot-stream frames buffered per peer (sender memory = "
        "window x chunk)"),
    "NOMAD_TPU_HEARTBEAT_BATCH_MS": Knob(
        "50", "float",
        "leader heartbeat-batcher flush interval (one "
        "`NodeHeartbeatBatch` raft entry per flush)"),
    "NOMAD_TPU_HB_PENDING_MAX": Knob(
        "8192", "int",
        "heartbeat-batcher pending cap; at the cap the writer forces "
        "a flush"),
    "NOMAD_TPU_INTEGRITY_INTERVAL": Knob(
        "2.0", "float",
        "seconds between leader `STATE_CHECKPOINT` proposals (replica "
        "digest votes); <= 0 disables the integrity plane"),
    "NOMAD_TPU_INTEGRITY_FULL_EVERY": Knob(
        "4", "int",
        "every Nth checkpoint full-walks all tables (ground truth for "
        "divergence conviction; between them digests are incremental)"),
    "NOMAD_TPU_FLEET_AGENTS": Knob(
        "10000", "int",
        "in-process client agents the `fleet_soak` bench cells "
        "register and heartbeat"),
    # -- overload control --------------------------------------------
    "NOMAD_TPU_DEFAULT_DEADLINE": Knob(
        "", "float",
        "ingress budget (s) when no `X-Nomad-Deadline` header; empty "
        "= no default deadline"),
    "NOMAD_TPU_ADMIT_RATE": Knob(
        "0", "float",
        "admission tokens/sec refilled per namespace (`0` = off)"),
    "NOMAD_TPU_ADMIT_BURST": Knob(
        "0", "float",
        "admission bucket capacity (`0` = 2x rate)"),
    "NOMAD_TPU_ADMIT_CONCURRENCY": Knob(
        "0", "int",
        "in-flight requests per namespace (`0` = off)"),
    "NOMAD_TPU_BROWNOUT_DEPTH": Knob(
        "256", "int",
        "proposal-queue depth at the brownout edge"),
    "NOMAD_TPU_BROWNOUT_LAG": Knob(
        "512", "int",
        "commit->apply lag (entries) at the brownout edge"),
    # -- event streaming ---------------------------------------------
    "NOMAD_TPU_SUB_QUEUE": Knob(
        "1024", "int",
        "per-subscriber event queue depth before the subscriber is "
        "marked lagging"),
    "NOMAD_TPU_EVENT_BUFFER": Knob(
        "256", "int",
        "retained event-broker ring size (catch-up window)"),
    "NOMAD_TPU_STREAM_HEARTBEAT": Knob(
        "1.0", "float",
        "blocking-stream heartbeat interval (s), per-request "
        "overridable"),
    # -- observability / fault injection -----------------------------
    "NOMAD_TPU_TRACE": Knob(
        "", "bool",
        "install a process-wide tracer at import (`1` to enable)"),
    "NOMAD_TPU_TRACE_SAMPLE": Knob(
        "1.0", "float",
        "trace sampling rate in [0, 1]"),
    "NOMAD_TPU_CHAOS": Knob(
        "", "str",
        "chaos-injection spec (`seed=42;rpc.drop=0.05;...`), empty = "
        "disabled"),
    # -- native library ----------------------------------------------
    "NOMAD_TPU_NATIVE_LIB": Knob(
        "", "str",
        "path override for the nomad_native shared library (empty = "
        "build-dir discovery)"),
    "NOMAD_TPU_NATIVE_BREAKER": Knob(
        "3", "int",
        "native-call circuit breaker: consecutive faults before "
        "falling back to pure-python"),
    # -- misc --------------------------------------------------------
    "NOMAD_TPU_ACL": Knob(
        "", "bool",
        "`1` enables ACL enforcement at boot (`server.enable_acl()`)"),
    "NOMAD_TPU_TEMPLATE_POLL_S": Knob(
        "0.5", "float",
        "task template re-render poll interval (s)"),
    "NOMAD_TPU_JAX_CACHE": Knob(
        "1", "bool",
        "`0` disables the persistent jax compilation cache (it lives "
        "where `JAX_COMPILATION_CACHE_DIR` says, else at "
        "`<checkout>/.jax_cache`)"),
}

_FALSE_STRINGS = ("", "0", "false", "no", "off")


def _raw(name: str, env: Optional[Mapping[str, str]]) -> tuple:
    try:
        knob = KNOBS[name]
    except KeyError:
        raise KeyError(
            f"unregistered knob {name!r}: declare it in "
            f"nomad_tpu/knobs.py KNOBS") from None
    src: Mapping[str, str] = os.environ if env is None else env
    val = src.get(name)
    if val is None or val == "":
        return None, knob
    return val, knob


def get_str(name: str, default: Optional[str] = None,
            env: Optional[Mapping[str, str]] = None) -> str:
    """The knob's raw string value ("" when unset and no default)."""
    raw, knob = _raw(name, env)
    if raw is not None:
        return raw
    return knob.default if default is None else default


def get_int(name: str, default: Optional[int] = None,
            env: Optional[Mapping[str, str]] = None) -> Optional[int]:
    """The knob as an int; `None` when unset with an empty registry
    default and no `default=` (knobs where empty means "auto")."""
    raw, knob = _raw(name, env)
    if raw is not None:
        return int(raw)
    if default is not None:
        return default
    return int(knob.default) if knob.default else None


def get_float(name: str, default: Optional[float] = None,
              env: Optional[Mapping[str, str]] = None) -> Optional[float]:
    """The knob as a float; `None` when unset with an empty registry
    default and no `default=`."""
    raw, knob = _raw(name, env)
    if raw is not None:
        return float(raw)
    if default is not None:
        return default
    return float(knob.default) if knob.default else None


def get_bool(name: str, default: Optional[bool] = None,
             env: Optional[Mapping[str, str]] = None) -> bool:
    """The knob as a bool: "", "0", "false", "no", "off" (any case)
    are false, anything else true; unset falls back to `default=` then
    the registry default."""
    raw, knob = _raw(name, env)
    if raw is None:
        if default is not None:
            return default
        raw = knob.default
    return raw.strip().lower() not in _FALSE_STRINGS


@contextlib.contextmanager
def override(name: str, value) -> Iterator[None]:
    """Scope an environment override of a registered knob to a block
    (`None` unsets).  Restores the prior state on exit."""
    if name not in KNOBS:
        raise KeyError(
            f"unregistered knob {name!r}: declare it in "
            f"nomad_tpu/knobs.py KNOBS")
    old = os.environ.get(name)
    if value is None:
        os.environ.pop(name, None)
    else:
        os.environ[name] = str(value)
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = old


def markdown_table() -> str:
    """README knob table, one row per registered knob (the README
    copy is generated from here: ``python -m nomad_tpu.knobs``)."""
    rows = ["| knob | default | type | meaning |",
            "| --- | --- | --- | --- |"]
    for name, knob in KNOBS.items():
        default = f"`{knob.default}`" if knob.default else "unset"
        rows.append(f"| `{name}` | {default} | {knob.type} | "
                    f"{knob.doc} |")
    return "\n".join(rows)


if __name__ == "__main__":  # pragma: no cover - exercised via CLI
    print(markdown_table())
