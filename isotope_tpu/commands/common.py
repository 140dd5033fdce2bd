"""Shared flag plumbing for run-executing subcommands.

Detail-mode (per-segment fence) arming used to be duplicated across
``simulate``/``sweep`` (``--telemetry=detail``) and ``telemetry``
(``--detail``), so two callers in one process could CONFLICT — the
second ``enable(detail=False)`` silently stripped fences the first had
armed.  :func:`arm_telemetry` is the single composition point: detail
requests OR together (a later caller can add detail, never remove it),
which is what lets ``--telemetry=detail`` and the attribution pass (or
the ``telemetry`` probe's ``--detail``) compose.
"""
from __future__ import annotations

from typing import Optional


def arm_telemetry(mode: Optional[str] = None,
                  detail: bool = False) -> bool:
    """Arm engine telemetry emission/detail from command flags.

    ``mode`` is a ``--telemetry`` value (``None`` / ``"on"`` /
    ``"detail"``); ``detail`` is an independent detail request (the
    ``telemetry`` subcommand's ``--detail``).  Returns whether detail
    fencing is armed after this call.
    """
    from isotope_tpu import telemetry

    want_detail = bool(detail) or mode == "detail"
    if mode or want_detail:
        # compose, never strip: an earlier caller's detail request
        # survives a later plain --telemetry
        telemetry.enable(
            detail=want_detail or telemetry.detail_enabled()
        )
    return telemetry.detail_enabled()


def default_compile_cache(compile_cache: Optional[str],
                          mode: Optional[str]) -> Optional[str]:
    """The telemetry-run compile-cache default: plain ``--telemetry``
    runs measure cache effectiveness, so they ask for the persistent
    cache (``"on"`` — the directory is compiler/cache.py's one rule)
    unless the user said otherwise.  Detail mode is excluded — eager
    execution would fill the cache with per-primitive noise."""
    if mode == "on" and compile_cache is None:
        return "on"
    return compile_cache


def add_compile_cache_arg(parser) -> None:
    """``--compile-cache``, shared by every run-executing subcommand
    (the rule itself lives in compiler/cache.py)."""
    parser.add_argument(
        "--compile-cache", metavar="on|off|DIR", default=None,
        help="persistent XLA compilation cache: repeated runs of one "
             "topology family skip XLA.  The directory is "
             "$JAX_COMPILATION_CACHE_DIR where set, else DIR, else "
             "<checkout>/.xla-cache ('on'); 'off' disables.  Default: "
             "on only where the variable is set")
