"""Persistent XLA compilation cache wiring + the cache-key manifest.

Cold start is the single largest wall-clock line item after PR 1: a
restarted worker, a model swap in the comparison matrix, or an autoscale
event re-pays ~17 s of XLA compilation for executables that are
byte-identical to the previous process's. JAX ships a persistent
compilation cache (keyed by the HLO fingerprint, so stale reuse is
structurally impossible at the XLA layer); this module is the one place
that turns it on, resolves the cache directory, and records a
human-readable MANIFEST next to the opaque cache entries so operators can
see *what* a cache dir was warmed for (model config, quant mode, mesh,
bucket ladder) — the same key the engine's in-process executable registry
uses (engine/compile_plan.py).

Hit/miss observability: JAX emits monitoring events per backend compile
(`/jax/compilation_cache/compile_requests_use_cache` on every request
that consults the cache, `/jax/compilation_cache/cache_hits` on a disk
hit). ``install_cache_listener`` funnels those into the process-wide
counters that ``profiling.CompileStats`` snapshots per sweep.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import threading
import time
from pathlib import Path
from typing import Any, Dict, Optional, Sequence

from .logging import get_logger

log = get_logger(__name__)

# JAX's own variable: when it is set JAX already has that directory, and
# this module sets none (a machine that comes with it set keeps one cache
# across every program on it).
ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# Otherwise: ONE fixed path inside the checkout, derived from the
# package's own location (git-ignored). The path is part of JAX's cache
# key, so a directory that moves — a temp dir, a pid, a timestamp —
# never hits.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# Thread-name prefix of engine/compile_plan's precompile pool.
COMPILE_PLAN_THREADS = "compile-plan"

_state_lock = threading.Lock()
_enabled_dir: Optional[Path] = None
_listener_installed = False

# Process-wide persistent-cache counters (fed by the jax.monitoring
# listener). CompileStats.snapshot_persistent() diffs these per sweep.
_requests = 0
_hits = 0


def resolve_cache_dir(cache_dir: Optional[os.PathLike | str] = None
                      ) -> Optional[Path]:
    """The directory THIS code hands to JAX — the one place the rule
    lives. ``None`` when ``$JAX_COMPILATION_CACHE_DIR`` is set: JAX
    already has that directory and the code sets none (an explicit
    ``cache_dir`` is then ignored with a warning). Unset: the explicit
    ``cache_dir`` (tests that need a private one), else the fixed
    ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV_JAX_CACHE_DIR)
    if env:
        if cache_dir is not None:
            log.warning("%s=%s is set; ignoring the explicit compile "
                        "cache directory %s", ENV_JAX_CACHE_DIR, env,
                        cache_dir)
        return None
    if cache_dir is not None:
        return Path(cache_dir).expanduser()
    return REPO_CACHE_DIR


def _on_event(event: str, **kwargs) -> None:
    global _requests, _hits
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        _requests += 1
    elif event == "/jax/compilation_cache/cache_hits":
        _hits += 1
        # JAX reports on the compiling thread: the span open there (the
        # plan's engine/compile_load) learns that the disk served it.
        from ..observe import tracing

        tracing.annotate(persistent_cache_hit=True)


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    """A backend compile (or load from the persistent cache) on a thread
    that is not the compile plan's: trace-on-first-call. Recorded as the
    span ``engine/compile_lazy`` under whatever span is open there, so a
    compile inside a dispatch has a name and a culprit."""
    if (event.endswith("backend_compile_duration")
            and not threading.current_thread().name.startswith(
                COMPILE_PLAN_THREADS)):
        from ..observe import tracing

        now = time.monotonic()
        tracing.add_span("engine/compile_lazy", now - seconds, now)


def install_cache_listener() -> None:
    """Register the jax.monitoring listener feeding the hit/miss counters
    (idempotent — jax keeps every registered listener forever)."""
    global _listener_installed
    with _state_lock:
        if _listener_installed:
            return
        _listener_installed = True
    import jax

    jax.monitoring.register_event_listener(
        lambda event, **kw: _on_event(event))
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def persistent_cache_counters() -> Dict[str, int]:
    """(requests, hits, misses) since process start — the raw counters
    behind CompileStats' per-sweep deltas."""
    return {"requests": _requests, "hits": _hits,
            "misses": _requests - _hits}


def enable_persistent_cache(cache_dir: Optional[os.PathLike | str] = None,
                            *, min_compile_time_secs: float = 0.0
                            ) -> Optional[Path]:
    """Turn on JAX's persistent compilation cache (idempotent).

    Executables then survive process restarts: a warm worker deserializes
    ~instead of recompiling~ every bucket executable it already built in
    any previous life. ``min_compile_time_secs=0`` caches everything —
    the sweep's per-bucket programs are exactly the many-small-programs
    workload the default 1 s threshold would skip. The directory follows
    :func:`resolve_cache_dir`; with ``$JAX_COMPILATION_CACHE_DIR`` set
    only the thresholds, the hit/miss listener and the manifest location
    are set here. Returns the directory in effect, or None when it is
    unwritable — the engine then just compiles lazily, nothing breaks.
    """
    global _enabled_dir
    ours = resolve_cache_dir(cache_dir)
    path = (Path(os.environ[ENV_JAX_CACHE_DIR]).expanduser()
            if ours is None else ours)
    with _state_lock:
        if _enabled_dir == path:
            return path
    try:
        path.mkdir(parents=True, exist_ok=True)
        import jax

        if ours is not None:
            jax.config.update("jax_compilation_cache_dir", str(ours))
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_time_secs))
        if ours is not None:
            # jax initializes its cache object at most once per process
            # and has no config hook on the dir — reset so a changed dir
            # (tests enabling a private one) actually takes.
            from jax.experimental.compilation_cache import (
                compilation_cache as _cc)

            _cc.reset_cache()
    except OSError as err:  # the cache is an optimization
        log.warning("persistent compile cache unavailable (%s); "
                    "compiles will not survive restarts", err)
        return None
    install_cache_listener()
    with _state_lock:
        _enabled_dir = path
    log.info("persistent compile cache: %s%s", path,
             "" if ours is not None else f" (from ${ENV_JAX_CACHE_DIR})")
    return path


def enabled_cache_dir() -> Optional[Path]:
    return _enabled_dir


def disable_persistent_cache() -> None:
    """Turn the persistent cache back off (tests; --no-compile-cache is
    handled by simply never enabling)."""
    global _enabled_dir
    import jax
    from jax.experimental.compilation_cache import compilation_cache as _cc

    jax.config.update("jax_compilation_cache_dir", None)
    _cc.reset_cache()
    with _state_lock:
        _enabled_dir = None


# ---------------------------------------------------------------------------
# Cache-key manifest
# ---------------------------------------------------------------------------

def _canonical(obj: Any) -> Any:
    """Stable JSON-able projection: dataclasses -> sorted dicts, paths ->
    str, tuples -> lists. Unknown objects hash by repr (stable within a
    release — good enough for a cache KEY whose collisions only cost a
    recompile check, never a wrong result: the XLA layer re-keys by HLO)."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {f.name: _canonical(getattr(obj, f.name))
                for f in dataclasses.fields(obj)}
    if isinstance(obj, dict):
        return {str(k): _canonical(v) for k, v in sorted(obj.items())}
    if isinstance(obj, (list, tuple)):
        return [_canonical(v) for v in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    if isinstance(obj, os.PathLike):
        return str(obj)
    return repr(obj)


def quant_mode(params: Any) -> str:
    """Quantization fingerprint of a param tree: which leaf flavors it
    holds (QuantTensor static fields change the compiled program — a
    cache warmed for int8 weights must not look reusable for bf16)."""
    import jax

    from ..models import quant as quant_mod

    kinds = set()
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, quant_mod.QuantTensor)):
        if isinstance(leaf, quant_mod.QuantTensor):
            kinds.add("int8-dyn" if getattr(leaf, "dynamic", False)
                      else "int8")
        else:
            kinds.add(str(getattr(leaf, "dtype", type(leaf).__name__)))
    return "+".join(sorted(kinds)) or "empty"


def manifest_key(cfg: Any, runtime: Any, *, buckets: Sequence[int],
                 quant: str = "fp", mesh: Any = None) -> str:
    """16-hex cache key over everything that determines executable shapes:
    model config, runtime decode knobs, quant mode, mesh shape, and the
    bucket ladder. Any change produces a different key, so a registry (or
    a manifest entry) built for one configuration can never serve
    another — stale reuse is impossible by construction."""
    payload = {
        "model": _canonical(cfg),
        "runtime": _canonical(runtime),
        "buckets": [int(b) for b in buckets],
        "quant": quant,
        "mesh": _canonical(mesh),
    }
    blob = json.dumps(payload, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def write_manifest(key: str, payload: Dict[str, Any],
                   cache_dir: Optional[Path] = None) -> Optional[Path]:
    """Record what a cache was warmed for: ``manifest-<key>.json`` in the
    cache dir (first writer wins; the content is a function of the key).
    No-op when no persistent cache is enabled."""
    root = cache_dir or _enabled_dir
    if root is None:
        return None
    path = Path(root) / f"manifest-{key}.json"
    if path.exists():
        return path
    try:
        tmp = path.with_suffix(".tmp")
        tmp.write_text(json.dumps(
            {"key": key, **{k: _canonical(v) for k, v in payload.items()}},
            indent=2, sort_keys=True))
        tmp.replace(path)
    except OSError as err:
        log.warning("could not write cache manifest %s (%s)", path, err)
        return None
    return path
