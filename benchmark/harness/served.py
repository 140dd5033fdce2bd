"""The system under test, driven the way an operator drives it: the
CLI's own ``main(argv)``, in this process (a child could not share the
chip), with its artifacts read back from disk.

Copied from chip_smoke.py (``_cli``, ``_incoming_totals``) and widened to
every series the conservation check reads; PERF.md lists the original
under Open questions."""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import io
import json
import os
import re
import time
from typing import Dict, List, Optional, Tuple

_SAMPLE = re.compile(r'^([a-zA-Z_:][a-zA-Z0-9_:]*)\{([^}]*)\} (\S+)$')
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="([^"]*)"')


@dataclasses.dataclass
class Call:
    """One served call: what was asked, and what came back."""

    index: int
    seed: int
    argv: List[str]
    tmp: str
    rc: Optional[int] = None
    stdout: str = ""
    t0: float = 0.0
    t1: float = 0.0

    @property
    def wall_s(self) -> float:
        return self.t1 - self.t0


def substitute(text: str, values: Dict[str, str]) -> str:
    for key, value in values.items():
        text = text.replace(key, value)
    return text


def prepare(template: dict, values: Dict[str, str]) -> List[str]:
    """Render the traffic mix's files and argv for one call."""
    for spec in (template.get("render") or {}).values():
        with open(substitute(spec["from"], values)) as f:
            text = f.read()
        for old, new in spec["replace"]:
            if text.count(old) != 1:
                raise ValueError(
                    f"{spec['from']}: {old!r} occurs {text.count(old)} "
                    f"times, want exactly once")
            text = text.replace(old, substitute(new, values))
        with open(substitute(spec["to"], values), "w") as f:
            f.write(text)
    return [substitute(a, values) for a in template["argv"]]


def run_cli(call: Call) -> Call:
    """``isotope-tpu <argv>`` in-process.  The wall runs from the
    invocation to the return of ``main``, by which time the artifacts
    are on disk: the CLI has read the summary back from the device to
    write them."""
    from isotope_tpu import cli

    out = io.StringIO()
    with open(os.path.join(call.tmp, "stderr.log"), "w") as err:
        call.t0 = time.perf_counter()
        try:
            with contextlib.redirect_stdout(out), \
                    contextlib.redirect_stderr(err):
                call.rc = cli.main(call.argv)
        except SystemExit as e:   # argparse refuses with SystemExit(2)
            call.rc = e.code if isinstance(e.code, int) else 1
        call.t1 = time.perf_counter()
    call.stdout = out.getvalue()
    return call


def stderr_tail(call: Call, n: int = 2000) -> str:
    try:
        with open(os.path.join(call.tmp, "stderr.log")) as f:
            return f.read()[-n:]
    except OSError:
        return ""


def read_exposition(path: str) -> Dict[str, Dict[Tuple, float]]:
    """{family: {label values, in the order written: value}} for every
    labelled, non-bucket sample of a Prometheus text exposition."""
    out: Dict[str, Dict[Tuple, float]] = {}
    with open(path) as f:
        for line in f:
            if line.startswith("#") or "_bucket{" in line:
                continue
            m = _SAMPLE.match(line.rstrip("\n"))
            if m is None:
                continue
            labels = tuple(v for _, v in _LABEL.findall(m.group(2)))
            out.setdefault(m.group(1), {})[labels] = float(m.group(3))
    return out


def read_buckets(path: str, family: str) -> Dict[Tuple, List[Tuple]]:
    """{label values without ``le``: [(upper edge, cumulative count)]}
    for one histogram family, edges in the order written (+Inf last)."""
    out: Dict[Tuple, List[Tuple]] = {}
    prefix = family + "_bucket{"
    with open(path) as f:
        for line in f:
            if not line.startswith(prefix):
                continue
            m = _SAMPLE.match(line.rstrip("\n"))
            if m is None:
                continue
            labels = _LABEL.findall(m.group(2))
            key = tuple(v for k, v in labels if k != "le")
            edge = float(dict(labels)["le"].replace("+Inf", "inf"))
            out.setdefault(key, []).append((edge, float(m.group(3))))
    return out


def artifacts(call: Call, spec: dict, values: Dict[str, str]):
    """[(label, fortio doc, exposition path)] for each run of the call,
    and the list of files the mix names that are missing."""
    missing = [p for p in (substitute(r, values)
                           for r in spec.get("required", ()))
               if not os.path.exists(p)]
    proms = sorted(glob.glob(substitute(spec["prometheus"], values)))
    if spec["fortio"] == "stdout":
        try:
            docs = [json.loads(call.stdout)]
        except ValueError:
            docs = []
    else:
        docs = []
        for p in sorted(glob.glob(substitute(spec["fortio"], values))):
            with open(p) as f:
                docs.append(json.load(f))
    runs = []
    for i in range(max(len(docs), len(proms))):
        doc = docs[i] if i < len(docs) else None
        prom = proms[i] if i < len(proms) else None
        label = (doc or {}).get("Labels") or (
            os.path.basename(prom) if prom else f"run{i}")
        runs.append((label, doc, prom))
    return runs, missing
