"""Trace-file summarizer: ``python -m repro.obs.report TRACE.jsonl``.

Reads a JSONL trace written by ``obs/trace.py`` and prints:

* the commit+env meta line the trace is keyed by;
* per-phase wall-time summary (count, total, p50, p95), sorted by
  total descending;
* top compile offenders — spans during which JAX lowered a program
  (the ``compiled`` attribute, from the ``jax.compiles`` counter),
  sorted by duration;
* the counter/gauge/observation snapshot.

``--json`` emits the same content as one JSON object for tooling.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List

from repro.obs.trace import load_jsonl, phase_summary


def compile_offenders(spans: List[Dict], top: int = 10) -> List[Dict]:
    """Spans that triggered a fresh jit compile, slowest first."""
    hits = [s for s in spans if (s.get("attrs") or {}).get("compiled")]
    hits.sort(key=lambda s: s["dur"], reverse=True)
    return [{"name": s["name"], "dur_s": s["dur"],
             "attrs": {k: v for k, v in (s.get("attrs") or {}).items()
                       if k != "compiled"}}
            for s in hits[:top]]


def summarize(path: str, top: int = 10) -> Dict:
    """Everything the CLI prints, as one dict (used by bench smoke)."""
    meta, spans, metrics = load_jsonl(path)
    return {"meta": meta,
            "phases": phase_summary(spans),
            "compile_offenders": compile_offenders(spans, top=top),
            "metrics": metrics}


def render(rep: Dict, out=None) -> None:
    out = out or sys.stdout
    w = out.write
    meta = rep["meta"]
    w(f"# trace commit={meta.get('commit', '?')} "
      f"python={meta.get('python', '?')} jax={meta.get('jax', '?')} "
      f"at={meta.get('timestamp', '?')}\n")
    w("phase,count,total_s,p50_s,p95_s\n")
    phases = sorted(rep["phases"].items(),
                    key=lambda kv: kv[1]["total_s"], reverse=True)
    for name, p in phases:
        w(f"{name},{p['count']},{p['total_s']:.6f},"
          f"{p['p50_s']:.6f},{p['p95_s']:.6f}\n")
    if rep["compile_offenders"]:
        w("# top compile offenders (fresh jit cache entries)\n")
        for o in rep["compile_offenders"]:
            extra = "".join(f" {k}={v}" for k, v in o["attrs"].items())
            w(f"compile,{o['name']},{o['dur_s']:.6f}{extra}\n")
    m = rep.get("metrics") or {}
    for kind in ("counters", "gauges", "observations"):
        for name, v in (m.get(kind) or {}).items():
            if isinstance(v, dict):
                body = ",".join(f"{k}={v[k]:.6g}" if
                                isinstance(v[k], float) else f"{k}={v[k]}"
                                for k in sorted(v))
            else:
                body = str(v)
            w(f"metric,{kind},{name},{body}\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="summarize a repro.obs JSONL trace")
    ap.add_argument("trace", help="path to a trace .jsonl file")
    ap.add_argument("--top", type=int, default=10,
                    help="compile offenders to show")
    ap.add_argument("--json", dest="as_json", action="store_true")
    args = ap.parse_args(argv)
    rep = summarize(args.trace, top=args.top)
    if args.as_json:
        print(json.dumps(rep))
    else:
        render(rep)
    return 0


if __name__ == "__main__":
    sys.exit(main())
