"""Process-environment perf preset for launchers (DESIGN.md §11).

The megakernel benchmarks are sensitive to three process-level knobs that
no amount of in-graph work can fix after the interpreter is up:

  * tcmalloc — host allocations (input pipeline, jit bookkeeping) are
    measurably faster under tcmalloc, but LD_PRELOAD only takes effect at
    exec time, so the preset either prints shell exports or re-execs the
    target command.
  * ``--xla_step_marker_location=1`` — puts the step marker at the outer
    while loop (0 = computation entry), so profiles and launch counts
    attribute per-step work to steps, not to the whole program.
  * log suppression (``TF_CPP_MIN_LOG_LEVEL=4``) and the tcmalloc large-
    alloc report threshold — both exist to keep benchmark stdout parseable.

Usage:
    eval "$(python -m repro.launch.env --sh)"         # current shell
    python -m repro.launch.env -- python -m repro.launch.train ...
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import Dict, Optional

# candidate tcmalloc shared objects, most specific first (the exact path
# varies by distro; LD_PRELOAD of a missing path breaks every child exec,
# so the preset only sets it when one actually exists)
TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
    "/usr/lib/libtcmalloc_minimal.so.4",
)

# XLA flags the preset guarantees are present (merged with any caller-set
# XLA_FLAGS; caller wins on conflicting values of the same flag)
XLA_PERF_FLAGS = ("--xla_step_marker_location=1",)


def find_tcmalloc() -> Optional[str]:
    for path in TCMALLOC_CANDIDATES:
        if os.path.exists(path):
            return path
    return None


def _merge_xla_flags(existing: str) -> str:
    have = {f.split("=", 1)[0] for f in existing.split() if f}
    add = [f for f in XLA_PERF_FLAGS if f.split("=", 1)[0] not in have]
    return " ".join(add + existing.split())


def perf_env(base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """The preset as a {name: value} delta over ``base`` (default
    ``os.environ``). Only returns keys whose value should change; never
    clobbers a caller-set XLA flag of the same name."""
    base = dict(os.environ if base is None else base)
    env: Dict[str, str] = {}
    tc = find_tcmalloc()
    if tc is not None:
        preload = base.get("LD_PRELOAD", "")
        if tc not in preload.split(os.pathsep):
            env["LD_PRELOAD"] = (tc + os.pathsep + preload if preload
                                 else tc)
    env.setdefault("TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD", "60000000000")
    if "TF_CPP_MIN_LOG_LEVEL" not in base:
        env["TF_CPP_MIN_LOG_LEVEL"] = "4"
    merged = _merge_xla_flags(base.get("XLA_FLAGS", ""))
    if merged != base.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = merged
    return env


def apply(environ: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Apply the preset in place (default: ``os.environ``) and return the
    delta that was applied. NOTE ``LD_PRELOAD`` and ``XLA_FLAGS`` only
    matter to processes exec'd AFTER this call — apply before importing
    jax, or use the CLI re-exec form."""
    environ = os.environ if environ is None else environ   # type: ignore
    delta = perf_env(dict(environ))
    environ.update(delta)
    return delta


# fixed in-checkout compile-cache path (listed in .gitignore): JAX keys
# cache entries by path too, so a moving directory would never hit
COMPILE_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first compile
    and return its directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is
    read by JAX itself and nothing else is set; otherwise the cache goes
    to the fixed ``<checkout>/.jax_cache``."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def _sh_quote(s: str) -> str:
    return "'" + s.replace("'", "'\\''") + "'"


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="print (or exec a command under) the perf env preset")
    ap.add_argument("--sh", action="store_true",
                    help="print eval-able `export K=V` lines")
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="-- command to exec with the preset applied")
    args = ap.parse_args(argv)
    cmd = args.cmd[1:] if args.cmd[:1] == ["--"] else args.cmd
    if cmd:
        env = dict(os.environ)
        env.update(perf_env(env))
        os.execvpe(cmd[0], cmd, env)
    delta = perf_env()
    for k in sorted(delta):
        if args.sh:
            print(f"export {k}={_sh_quote(delta[k])}")
        else:
            print(f"{k}={delta[k]}")


if __name__ == "__main__":
    main()
