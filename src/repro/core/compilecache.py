"""Persistent XLA compilation-cache wiring + compile accounting.

Cold campaign runs pay XLA twice over: once to trace/lower each dispatch
bucket and once to compile it (BENCH_campaign.json: ``sweep_fused_cold``
at roughly half of ``sweep_fused`` steady-state throughput).  The AOT
path in :mod:`repro.core.experiment` kills the in-process share of that
tax; this module kills the cross-process share by pointing JAX's
persistent compilation cache at a stable on-disk directory, so a second
process running the same spec deserialises every executable instead of
invoking XLA.

* :func:`enable_persistent_cache` — point
  ``jax_compilation_cache_dir`` at :func:`default_cache_dir` (or an
  explicit path) and drop the min-compile-time threshold so every
  campaign core is cached.  Idempotent; safe to call repeatedly.
* :func:`ensure_persistent_cache` — the lazy entry point
  ``experiment.execute`` calls: enables the default cache once per
  process unless JAX's cache is switched off or a directory was
  already chosen explicitly.
* :func:`xla_compile_stats` — process-wide counters of persistent-cache
  compile requests / hits / misses, fed by a ``jax.monitoring`` event
  listener.  ``misses`` counts ACTUAL XLA compiles; a warm-disk re-run
  of a spec reports ``misses == 0`` (pinned by
  ``tests/test_aot.py::test_disk_cache_second_process_zero_xla_compiles``).
* :func:`load_executable` / :func:`store_executable` — a persistent
  WHOLE-EXECUTABLE cache on top of XLA's module cache: the AOT path
  (:func:`repro.core.campaign.aot_executable`) serialises each compiled
  bucket executable (``jax.experimental.serialize_executable``) under
  ``<cache>/executables/<jax+backend+source fingerprint>/``, so a warm
  re-run in a fresh process skips TRACING as well as XLA — XLA's own
  cache only short-circuits compilation, and for campaign-sized
  programs the Python trace/lower step costs seconds of its own.
  XLA's module cache leaves op metadata out of its key
  (``jax_compilation_cache_include_metadata_in_key`` is off), so an
  executable it serves may carry the ``jax.named_scope`` names of an
  older build of the same program.

Environment (JAX's own variables):

``JAX_COMPILATION_CACHE_DIR``
    The cache directory.  When set, it is used as given and no other
    directory is chosen.  Unset, the cache lives at a fixed path inside
    the checkout, ``<repo>/.repro-cache`` (:data:`REPO_CACHE_DIR`): the
    path is part of nothing that moves, so every process of a checkout
    finds the same entries.
``JAX_ENABLE_COMPILATION_CACHE=false``
    Switches the persistent cache off.
"""
from __future__ import annotations

import functools
import hashlib
import os
import pickle
import threading
from typing import Any, Dict, Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: ``.repro-cache`` at the root of the checkout (``src/repro/core`` up three)
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".repro-cache")
#: set REPRO_CACHE_DEBUG=1 to surface the otherwise-swallowed
#: executable-cache store/load failures on stderr
DEBUG_VAR = "REPRO_CACHE_DEBUG"


def _debug(msg: str, exc: Exception) -> None:
    if os.environ.get(DEBUG_VAR):
        import sys
        import traceback
        print(f"[repro.compilecache] {msg}: {exc!r}", file=sys.stderr)
        traceback.print_exc(file=sys.stderr)

#: monitoring events the listener folds into :func:`xla_compile_stats`.
_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_counts = {"requests": 0, "hits": 0, "exe_hits": 0, "exe_stores": 0}
_state = {"dir": None, "listening": False, "ensured": False}


def default_cache_dir() -> Optional[str]:
    """Resolved cache directory: ``JAX_COMPILATION_CACHE_DIR`` when set,
    else :data:`REPO_CACHE_DIR`; None when JAX's cache is switched off
    (``JAX_ENABLE_COMPILATION_CACHE=false``)."""
    if not jax.config.jax_enable_compilation_cache:
        return None
    env = os.environ.get(ENV_VAR)
    if env:
        return os.path.abspath(os.path.expanduser(env))
    return REPO_CACHE_DIR


def _listener(event: str, **kwargs) -> None:
    if event == _REQUEST_EVENT:
        with _lock:
            _counts["requests"] += 1
    elif event == _HIT_EVENT:
        with _lock:
            _counts["hits"] += 1


def _register_listener() -> None:
    if _state["listening"]:
        return
    from jax._src import monitoring
    monitoring.register_event_listener(_listener)
    _state["listening"] = True


def enable_persistent_cache(path: Optional[str] = None) -> Optional[str]:
    """Enable the on-disk compilation cache at ``path`` (default:
    :func:`default_cache_dir`); returns the directory in use, or None
    when JAX's cache is switched off.  Re-pointing at a new
    directory resets JAX's in-process handle so subsequent compiles hit
    the new location."""
    path = default_cache_dir() if path is None else os.path.abspath(
        os.path.expanduser(path))
    if path is None:
        return None
    _register_listener()
    if _state["dir"] == path:
        return path
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    # campaign cores compile in ~seconds but MUST be cached: drop the
    # default >1s threshold and the min-entry-size floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _reset_jax_cache_handle()
    _state["dir"] = path
    return path


def disable_persistent_cache() -> None:
    """Turn the persistent cache off for this process (tests use this
    to time genuinely cold compiles)."""
    jax.config.update("jax_compilation_cache_dir", None)
    _reset_jax_cache_handle()
    _state["dir"] = None
    _state["ensured"] = True   # an explicit choice: ensure() stays out


def ensure_persistent_cache() -> Optional[str]:
    """Lazy default wiring: the first ``execute()`` of a process lands
    here and enables the default directory, honouring JAX's off switch
    and never overriding an explicit
    :func:`enable_persistent_cache` / :func:`disable_persistent_cache`
    call made earlier."""
    if _state["ensured"] or _state["dir"] is not None:
        return _state["dir"]
    _state["ensured"] = True
    return enable_persistent_cache()


def persistent_cache_dir() -> Optional[str]:
    """The directory currently wired, or None when disabled."""
    return _state["dir"]


def _reset_jax_cache_handle() -> None:
    """Drop jax's in-process cache object so the next compile re-reads
    ``jax_compilation_cache_dir`` (private API; tolerated to be absent)."""
    try:
        from jax._src import compilation_cache as _cc
        _cc.reset_cache()
    except Exception:
        pass


# ---------------------------------------------------------------------------
# Whole-executable cache (the layer above XLA's module cache)
# ---------------------------------------------------------------------------
#: the package whose sources are digested into the executable-cache tag
_REPRO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=1)
def source_digest() -> str:
    """SHA-256 (first 16 hex digits) of every ``*.py`` under the
    ``repro`` package, by sorted relative path: cache entries are keyed
    by configuration, not by program, so the executable cache is tagged
    by the code that builds and wraps the cores."""
    paths = []
    for root, dirs, files in os.walk(_REPRO_DIR):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        paths += [os.path.join(root, f) for f in files if f.endswith(".py")]
    h = hashlib.sha256()
    for rel in sorted(os.path.relpath(p, _REPRO_DIR) for p in paths):
        with open(os.path.join(_REPRO_DIR, rel), "rb") as f:
            h.update(rel.encode() + b"\0" + f.read())
    return h.hexdigest()[:16]


def _exe_dir() -> Optional[str]:
    """Executable-cache directory, fingerprinted by the jax/jaxlib
    version and backend — a serialized executable only loads into the
    runtime that produced it, so upgrades silently start a fresh
    namespace instead of failing deserialisation — and by
    :func:`source_digest`, so an executable stored before a change to
    the program's code is never loaded after it."""
    d = _state["dir"]
    if d is None:
        return None
    import jaxlib
    tag = (f"jax-{jax.__version__}-jaxlib-{jaxlib.__version__}"
           f"-{jax.default_backend()}-src-{source_digest()}")
    return os.path.join(d, "executables", tag)


def exe_fingerprint(parts: Any) -> str:
    """Stable file name of one executable-cache entry.  ``parts`` is
    the campaign layer's canonical key + abstract-argument signature —
    all reprs of frozen dataclasses, strings, ints and tuples, so the
    digest is deterministic across processes."""
    return hashlib.sha256(repr(parts).encode()).hexdigest()


def load_executable(fingerprint: str) -> Optional[Any]:
    """Deserialise a previously stored compiled executable, or None on
    any miss (absent dir, absent entry, stale/undeserialisable payload
    — the cache must never turn into a failure mode)."""
    d = _exe_dir()
    if d is None:
        return None
    path = os.path.join(d, fingerprint + ".pkl")
    try:
        with open(path, "rb") as f:
            payload = pickle.load(f)
        from jax.experimental import serialize_executable as _se
        exe = _se.deserialize_and_load(*payload)
    except Exception as e:
        _debug(f"load_executable({fingerprint[:12]}) miss", e)
        return None
    with _lock:
        _counts["exe_hits"] += 1
    return exe


def store_executable(fingerprint: str, compiled: Any) -> None:
    """Serialise a compiled executable into the cache (atomic rename;
    best-effort — storage failures are silent, the executable in hand
    still runs)."""
    d = _exe_dir()
    if d is None:
        return
    try:
        from jax.experimental import serialize_executable as _se
        payload = _se.serialize(compiled)
        # an entry on disk must be an entry that LOADS: round-trip the
        # payload before persisting.  Serialisation can silently produce
        # an unloadable payload (an executable served from XLA's module
        # cache drops its jit-compiled symbols on the CPU — see
        # campaign._AOT_COMPILER_OPTIONS), and a poisoned entry would
        # force every future process through a fail-load + recompile.
        _se.deserialize_and_load(*payload)
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, fingerprint + ".pkl")
        tmp = f"{path}.tmp{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump(payload, f)
        os.replace(tmp, path)
    except Exception as e:
        _debug(f"store_executable({fingerprint[:12]}) failed", e)
        return
    with _lock:
        _counts["exe_stores"] += 1


def xla_compile_stats() -> Dict[str, int]:
    """Persistent-cache counters since process start (or the last
    :func:`reset_stats`): ``requests`` = compiles that consulted the
    disk cache, ``hits`` = served from disk, ``misses`` = actual XLA
    compiles that then populated it.  ``exe_hits`` / ``exe_stores``
    count whole-executable loads and writes (the AOT layer).  All zero
    while the cache is disabled (the events never fire)."""
    with _lock:
        c = dict(_counts)
    c["misses"] = c["requests"] - c["hits"]
    return c


def reset_xla_compile_stats() -> None:
    """Zero every counter behind :func:`xla_compile_stats`, opening a
    fresh observation window IN-PROCESS.  Analyzer runs and tests use
    this to assert zero-recompile windows (``misses == 0`` across a
    warm replay) without shelling out to a subprocess; pair with
    ``campaign.TRACE_COUNT`` deltas for the trace side."""
    with _lock:
        for k in _counts:
            _counts[k] = 0


#: legacy name (pre-plancheck); same window reset
reset_stats = reset_xla_compile_stats
