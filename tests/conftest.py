import os
import sys

# tests must see exactly ONE device: make sure no XLA_FLAGS (a forced host
# device count, say) leak into the test run
os.environ.pop("XLA_FLAGS", None)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import pytest  # noqa: E402  (sys.path fix must precede imports)

# ---------------------------------------------------------------------------
# Log-backend matrix: the recovery matrix (and everything else using the
# ``store_spec`` fixture) runs against a set of backend stacks selected by
# the LOGIO_STORE_SPEC env var — the CI matrix axis:
#
#   unset / "memory"  -> the four memory-family stacks (fast local default)
#   "sqlite"          -> durable sqlite stacks
#   "segment"         -> durable append-only segment stacks (checkpoint
#                        compaction runs live: tests/helpers.mk_store gives
#                        them a small checkpoint interval + segment size)
#   "sharded+group"   -> the epoch-flushing (2PC) sharded stacks
#   "all"             -> the union (nightly)
#   anything else     -> comma list of literal build_store specs
# ---------------------------------------------------------------------------

_SPEC_SETS = {
    "memory": ["memory", "memory+sharded", "memory+group",
               "memory+sharded+group"],
    "sqlite": ["sqlite", "sqlite+group"],
    "segment": ["segment", "segment+group"],
    "sharded+group": ["memory+sharded+group", "sqlite+sharded+group"],
}
_SPEC_SETS["all"] = (_SPEC_SETS["memory"] + _SPEC_SETS["sqlite"]
                     + _SPEC_SETS["segment"]
                     + ["sqlite+sharded+group", "segment+sharded+group"])


def active_store_specs():
    sel = os.environ.get("LOGIO_STORE_SPEC", "").strip()
    if not sel:
        return _SPEC_SETS["memory"]
    if sel in _SPEC_SETS:
        return _SPEC_SETS[sel]
    return [s.strip() for s in sel.split(",") if s.strip()]


@pytest.fixture(params=active_store_specs())
def store_spec(request):
    """Backend-stack spec string for ``tests.helpers.mk_store`` — the
    protocol must be oblivious to the storage stack behind LogBackend."""
    return request.param


# ---------------------------------------------------------------------------
# Process-transport matrix: process-mode tests run against the transports
# selected by the LOGIO_TRANSPORT env var — the CI matrix axis, mirroring
# LOGIO_STORE_SPEC:
#
#   unset             -> routed AND socket (fast local default; tcp-family
#                        coverage always runs via tests/test_multihost.py)
#   "all"             -> routed, socket, tcp AND shm (nightly cross)
#   "routed"          -> the supervisor-pumped pipe transport only
#   "socket"          -> the direct worker<->worker AF_UNIX transport only
#   "tcp"             -> the socket transport over AF_INET (host, port)
#   "shm"             -> shared-memory rings for co-located pairs (socket
#                        fallback across nodes)
#   anything else     -> comma list of literal transport names
# ---------------------------------------------------------------------------

_TRANSPORT_SETS = {
    "routed": ["routed"],
    "socket": ["socket"],
    "tcp": ["tcp"],
    "shm": ["shm"],
    "all": ["routed", "socket", "tcp", "shm"],
}


def active_transports():
    sel = os.environ.get("LOGIO_TRANSPORT", "").strip()
    if not sel:
        return ["routed", "socket"]
    if sel in _TRANSPORT_SETS:
        return _TRANSPORT_SETS[sel]
    return [t.strip() for t in sel.split(",") if t.strip()]


@pytest.fixture(params=active_transports())
def proc_transport(request):
    """Process-mode transport name — the recovery guarantees must be
    oblivious to how events move between workers."""
    return request.param


# ---------------------------------------------------------------------------
# Process-context matrix: process-mode tests run under the worker start
# methods selected by the LOGIO_PROC_CTX env var, mirroring LOGIO_TRANSPORT:
#
#   unset             -> fork where available, else spawn (fast local
#                        default; spawn coverage always runs via
#                        tests/test_multihost.py)
#   "all"             -> fork AND spawn (nightly runs the full
#                        fork x spawn x routed/socket/tcp cross)
#   "fork" / "spawn"  -> that start method only
#   anything else     -> comma list of literal start-method names
#
# spawn workers are rebuilt purely from the picklable WorkerBootstrap
# payload + the log — no fork inheritance — so this axis proves the
# recovery guarantees hold for workers started from durable state alone.
# ---------------------------------------------------------------------------

_CTX_SETS = {
    "fork": ["fork"],
    "spawn": ["spawn"],
    "all": ["fork", "spawn"],
}


def active_ctxs():
    import multiprocessing
    avail = multiprocessing.get_all_start_methods()
    sel = os.environ.get("LOGIO_PROC_CTX", "").strip()
    if not sel:
        return ["fork"] if "fork" in avail else ["spawn"]
    if sel == "all":
        # "whatever this platform has" — filtering is correct here
        return [c for c in _CTX_SETS["all"] if c in avail] or ["spawn"]
    if sel in _CTX_SETS:
        return _CTX_SETS[sel]
    return [c.strip() for c in sel.split(",") if c.strip()]


@pytest.fixture(params=active_ctxs())
def proc_ctx(request):
    """Process-mode worker start method (fork/spawn).  An explicitly
    requested method that this platform lacks skips loudly — a cell
    labeled fork must never silently go green by running spawn."""
    import multiprocessing
    if request.param not in multiprocessing.get_all_start_methods():
        pytest.skip(f"start method {request.param!r} unavailable here")
    return request.param
