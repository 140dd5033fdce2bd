"""AOT executable cache + persistent XLA compilation cache wiring.

Two layers keep repeated runs of the same topology family from paying
XLA again:

- **In-process executable cache** (:data:`executable_cache`): jitted
  entry points are stored process-wide, keyed by the engine's *shape
  signature* — the bucket plan bounds, request-block shape, load kind,
  feature flags, and a content digest of every constant the traced
  program closes over.  Re-instantiating a ``Simulator`` for the same
  compiled topology (same signature) reuses the already-traced — and,
  after first execution, already-compiled — function instead of
  retracing.  The digest makes sharing *sound*: two engines share an
  executable only when every baked constant is byte-identical.
- **Persistent on-disk cache** (:func:`enable_persistent_cache`): JAX's
  compilation cache, keyed by XLA on the optimized HLO, so separate
  *processes* (repeated CLI runs of one suite) skip the XLA backend compile entirely.  ONE rule picks
  the directory: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX
  already points there and this module sets no other; otherwise
  ``<checkout>/.xla-cache``, resolved from this package's location —
  the path is part of the cache key, so it never follows the cwd.
"""
from __future__ import annotations

import contextlib
import hashlib
import logging
import os
import pathlib
from collections import OrderedDict
from typing import Callable, List, Optional

from isotope_tpu import telemetry

logger = logging.getLogger(__name__)

#: JAX's own variable: when set, JAX reads the cache directory from it
#: at import and :func:`enable_persistent_cache` sets no other.
ENV_JAX_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

#: the fallback directory: ``<checkout>/.xla-cache``, anchored at the
#: package (never the cwd — a cache path that moves never hits)
DEFAULT_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[2] / ".xla-cache"
)

#: request values (case-insensitive) that disable the cache
_OFF = ("", "0", "off", "none")

#: sidecar recording each cache entry's content digest (scan_cache_dir)
DIGEST_SIDECAR = ".isotope-digests.json"
#: subdirectory corrupted entries are moved into (never deleted: a
#: quarantined entry is evidence, and XLA just retraces without it)
QUARANTINE_DIR = "quarantine"

_persistent_dir: Optional[str] = None
#: an explicit "off" holds against later un-asked enables (the sharded
#: runner's) until something asks again
_switched_off = False


def scan_cache_dir(path: str) -> dict:
    """Integrity-scan a persistent cache dir, quarantining bad entries.

    A corrupted entry (truncated write on a killed run, bit rot, a
    concurrent writer) used to surface as an unpickle/deserialize crash
    *inside* XLA's cache read — killing the run that was supposed to be
    saved compile time.  Every entry file is digested; an EMPTY file,
    an unreadable file, or one whose digest no longer matches the
    recorded sidecar digest is moved to ``<dir>/quarantine/`` (counter
    ``compile_cache_quarantined``) so XLA simply misses and retraces.
    Fresh entries get their digest recorded for the next scan.  Never
    raises — a broken cache must degrade to "no cache", not crash.

    JAX owns the directory and writes entries non-atomically, so a
    file another live process is still writing looks exactly like a
    torn one.  The scan therefore does NOT run when the cache is
    enabled (since PR 22) — only as the reaction to a corruption error
    this process actually hit while reading
    (``ExecutableCache._build_quarantining``), where a wrongly
    quarantined entry costs one recompile and the alternative is a
    crash.  JAX's ``*-atime`` bookkeeping files (rewritten on every
    read with eviction on) are never entries and are skipped.
    """
    stats = {"checked": 0, "quarantined": [], "recorded": 0}
    try:
        import json
        import shutil

        sidecar = os.path.join(path, DIGEST_SIDECAR)
        digests = {}
        try:
            with open(sidecar) as f:
                digests = json.load(f)
            if not isinstance(digests, dict):
                digests = {}
        except (OSError, ValueError):
            digests = {}  # missing/corrupt sidecar: rebuild from scratch
        qdir = os.path.join(path, QUARANTINE_DIR)
        fresh = {}
        for name in sorted(os.listdir(path)):
            fpath = os.path.join(path, name)
            if (
                name == DIGEST_SIDECAR
                or name.startswith(".")
                or name.endswith("-atime")
                or not os.path.isfile(fpath)
            ):
                continue
            stats["checked"] += 1
            digest = None
            try:
                with open(fpath, "rb") as f:
                    data = f.read()
                if data:
                    digest = hashlib.sha256(data).hexdigest()
            except OSError:
                digest = None
            bad = digest is None or (
                name in digests and digests[name] != digest
            )
            if bad:
                os.makedirs(qdir, exist_ok=True)
                try:
                    shutil.move(fpath, os.path.join(qdir, name))
                except OSError:  # pragma: no cover - best effort
                    try:
                        os.unlink(fpath)
                    except OSError:
                        continue
                stats["quarantined"].append(name)
                telemetry.counter_inc("compile_cache_quarantined")
                logger.warning(
                    "quarantined corrupted compile-cache entry %s "
                    "(%s) — it will be retraced", name,
                    "unreadable/empty" if digest is None
                    else "digest mismatch",
                )
            else:
                fresh[name] = digest
        stats["recorded"] = len(fresh)
        tmp = sidecar + ".tmp"
        with open(tmp, "w") as f:
            json.dump(fresh, f)
        os.replace(tmp, sidecar)
    except Exception:  # pragma: no cover - scan must never kill a run
        logger.warning("compile-cache scan failed", exc_info=True)
    return stats


def persistent_cache_dir() -> Optional[str]:
    """The currently wired persistent cache dir (None when disabled)."""
    return _persistent_dir


def enable_persistent_cache(request: Optional[str] = None) -> Optional[str]:
    """Turn JAX's persistent compilation cache on, by the one rule.

    ``request`` says WHETHER, the rule says WHERE:

    - ``None`` — not asked: on only where ``JAX_COMPILATION_CACHE_DIR``
      is set (the environment asked), else a no-op returning ``None``;
    - ``"off"`` / ``"0"`` / ``"none"`` / ``""`` — disabled (JAX's own
      cache is switched off too when the variable is set);
    - ``"on"`` — asked (bench, ``--telemetry`` runs, ``chip_smoke.py``);
    - anything else — asked, naming a directory for the case where the
      variable is unset.

    The directory: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    already points there — that directory serves and JAX's own
    setting is left untouched; otherwise the named
    directory, else :data:`DEFAULT_CACHE_DIR`.  Idempotent — safe to
    call from every entry point (bench, CLI, sharded runner).
    """
    global _persistent_dir, _switched_off
    env_dir = os.environ.get(ENV_JAX_CACHE_DIR)
    asked = None if request is None else str(request).strip()
    if asked is not None and asked.lower() in _OFF:
        if env_dir:
            import jax

            jax.config.update("jax_enable_compilation_cache", False)
        _persistent_dir = None
        _switched_off = True
        return None
    if asked is None and (_switched_off or not env_dir):
        return None
    _switched_off = False
    if env_dir:
        path = env_dir
    elif asked.lower() == "on":
        path = DEFAULT_CACHE_DIR
    else:
        path = os.path.abspath(os.path.expanduser(asked))
    if _persistent_dir == path:
        return path
    # persistent-cache hit/miss counts come from jax's own monitoring
    # events — subscribe before anything compiles through the cache
    telemetry.install_jax_hooks()
    import jax

    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_enable_compilation_cache", True)
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", path)
        # jax initializes its cache object lazily ONCE; re-pointing the
        # dir after something already compiled needs an explicit reset
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc,
        )

        _cc.reset_cache()
    # cache every entry: the sweep programs are exactly the long-compile
    # artifacts the cache exists for, and tiny entries are harmless
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _persistent_dir = path
    return path


def array_digest(*chunks) -> str:
    """SHA-256 over a heterogeneous sequence of arrays / reprs.

    Used to fingerprint every constant a traced program bakes in:
    NumPy (or JAX) arrays hash their raw bytes + shape + dtype, and
    anything else hashes its ``repr``.  ``None`` entries are skipped.
    """
    import numpy as np

    h = hashlib.sha256()
    hashed = 0
    for c in chunks:
        if c is None:
            continue
        a = None
        if isinstance(c, np.ndarray):
            a = c
        elif hasattr(c, "__array__") and not isinstance(c, (str, bytes)):
            a = np.asarray(c)
        if a is not None:
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
            h.update(np.ascontiguousarray(a).tobytes())
            hashed += a.nbytes
        else:
            text = repr(c).encode()
            h.update(text)
            hashed += len(text)
    telemetry.counter_inc("signature_bytes_hashed", hashed)
    return h.hexdigest()


class ExecutableCache:
    """Process-wide LRU of jitted entry points, keyed by shape signature.

    The stored value is the ``jax.jit``-wrapped callable; JAX's own jit
    cache then holds the compiled executable behind it, so a signature
    hit skips both retracing AND recompiling.

    Retention caveat: each entry's closure pins its builder Simulator's
    device constants until eviction, so ``max_entries`` bounds how many
    otherwise-dead engines a long multi-topology sweep keeps resident —
    sized for a sweep's load-shape grid over a few topologies, not a
    museum of every graph ever built.  Call :meth:`clear` to release
    everything (e.g. between unrelated experiments in one process).

    Thrash caveat: a sweep walks its keys in a fixed order, and a
    cyclic walk over MORE keys than an LRU holds misses every time: a
    sweep whose grid needs more than ``max_entries`` programs re-traces
    and re-lowers all of them on every pass of the same process (a
    suite, a regression loop, back-to-back calls).  Until PR 50 the
    latency envelope (5 environments x 6 connection counts) was such a
    sweep: 48 keys, 16 evictions a pass.  :meth:`cache_stats` says so
    (``thrashing``), :meth:`watch` counts the keys one sweep resolved,
    and ``sweep`` warns on stderr when a single sweep evicts.
    """

    def __init__(self, max_entries: int = 32):
        self.max_entries = max_entries
        self._fns: "OrderedDict[tuple, object]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self._watched: Optional[set] = None

    @contextlib.contextmanager
    def watch(self):
        """The distinct keys resolved (hit or built) while the block
        runs, as a set filled as they come: what ``sweep`` reports as
        its programs."""
        outer, self._watched = self._watched, set()
        try:
            yield self._watched
        finally:
            if outer is not None:
                outer |= self._watched
            self._watched = outer

    @staticmethod
    def key_digest(key: tuple) -> str:
        """Short stable digest of a cache key (log/stats identity)."""
        return hashlib.sha256(repr(key).encode()).hexdigest()[:12]

    def get_or_build(self, key: tuple, build: Callable[[], object]):
        if self._watched is not None:
            self._watched.add(key)
        if key in self._fns:
            self.hits += 1
            telemetry.counter_inc("executable_cache_hits")
            self._fns.move_to_end(key)
            return self._fns[key]
        self.misses += 1
        telemetry.counter_inc("executable_cache_misses")
        fn = self._build_quarantining(build)
        self._fns[key] = fn
        while len(self._fns) > self.max_entries:
            self._fns.popitem(last=False)
            self.evictions += 1
            telemetry.counter_inc("executable_cache_evictions")
        telemetry.gauge_set("executable_cache_entries", len(self._fns))
        logger.debug(
            "executable-cache miss #%d key=%s (hits=%d entries=%d)",
            self.misses, self.key_digest(key), self.hits, len(self._fns),
        )
        return fn

    def get_or_jit(self, key: tuple, name: str, fun: Callable,
                   **jit_kwargs):
        """The jitted entry point for ``key``, its first call
        phase-timed (telemetry.time_first_call).

        The XLA module is named ``jit_<name>_<key digest>`` — one name
        per distinct traced program, so a device profile's ``XLA
        Modules`` line and ``telemetry.program_scopes()`` tell the
        programs of one process apart."""
        def build():
            import jax

            def entry(*args, **kwargs):
                return fun(*args, **kwargs)

            entry.__name__ = entry.__qualname__ = (
                f"{name}_{self.key_digest(key)[:6]}"
            )
            return telemetry.time_first_call(
                jax.jit(entry, **jit_kwargs), "compile.jit_first_call"
            )

        return self.get_or_build(key, build)

    @staticmethod
    def _build_quarantining(build: Callable[[], object]):
        """Build an entry, absorbing corrupted persistent-cache reads.

        A digest-mismatch / unpickle failure surfacing from the
        persistent cache is the one DETERMINISTIC error with a better
        move than failing: quarantine the bad entries (scan_cache_dir)
        and retrace once.  Everything else propagates untouched.
        """
        from isotope_tpu.resilience import faults, taxonomy

        try:
            faults.check("cache.load")
            return build()
        except Exception as e:
            if not taxonomy.is_cache_corruption(e):
                raise
            telemetry.counter_inc("compile_cache_quarantine_retries")
            logger.warning(
                "corrupted persistent-cache entry (%s) — quarantining "
                "and retracing", e,
            )
            if _persistent_dir is not None:
                scan_cache_dir(_persistent_dir)
            return build()

    def cache_stats(self) -> dict:
        """Introspection: counts plus the resident keys' digests."""
        keys: List[str] = [self.key_digest(k) for k in self._fns]
        return {
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "entries": len(self._fns),
            "max_entries": self.max_entries,
            # a full cache that has evicted: a grid walked again in
            # this process re-compiles what it dropped (the docstring)
            "thrashing": bool(
                self.evictions and len(self._fns) >= self.max_entries
            ),
            "keys": keys,
        }

    def reset_stats(self) -> None:
        """Zero the counters WITHOUT dropping entries (test hook)."""
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __contains__(self, key: tuple) -> bool:
        return key in self._fns

    def __len__(self) -> int:
        return len(self._fns)

    def clear(self) -> None:
        self._fns.clear()
        self.hits = 0
        self.misses = 0
        self.evictions = 0


#: the process-wide instance every Simulator / ShardedSimulator consults
executable_cache = ExecutableCache()


def cache_stats() -> dict:
    """Stats of the process-wide executable cache (see ExecutableCache)."""
    return executable_cache.cache_stats()
