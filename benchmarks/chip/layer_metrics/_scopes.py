"""The by-scope reduction of a traced run's capture, shared by the readers
that time the program's own stages (not a reader: the name starts with _).

The capture is found through the engine probe (``health_end.last_profile``),
reduced once by ``benchmarks.chip.scopes`` in a process of its own on the CPU
(the worker has let go of the chip by then) and kept beside the capture, so
the second reader loads what the first one made.  A program without the
scopes, the probe entry or the capture gives None, never an error."""

import glob
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DENSE = ("qkv_proj", "o_proj", "mlp", "lm_head")


def summary(ctx):
    last = (ctx.get("health_end") or {}).get("last_profile") or {}
    trace_dir = last.get("trace_dir")
    if not trace_dir or not os.path.isdir(trace_dir):
        return None
    out = os.path.join(trace_dir, "scopes_summary.json")
    if not os.path.exists(out):
        files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                                 recursive=True))
        if not files:
            return None
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
        p = subprocess.run([sys.executable, "-m", "benchmarks.chip.scopes",
                            files[-1], out], env=env, cwd=ROOT,
                           capture_output=True, text=True, timeout=300)
        if p.returncode != 0 or not os.path.exists(out):
            print(json.dumps({"scopes_reduce_failed":
                              (p.stderr or "")[-2000:]}), flush=True)
            return None
    with open(out) as f:
        return json.load(f)


def decode_step_ms(ctx, scopes):
    """Milliseconds of the ops in ``scopes`` per run of a decode program
    (``jit_window``), over all its runs lying wholly inside the capture."""
    from benchmarks.chip.scopes import program_scope_ms

    return program_scope_ms(summary(ctx), "window", scopes)
