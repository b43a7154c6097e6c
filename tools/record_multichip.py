"""Record the multi-chip dryrun as a round artifact, BYTE-IDENTICAL to
the driver's rewrite.

Four rounds in a row the working tree showed ``M MULTICHIP_r*.json``
after a driver re-run: the builder stamped a ``git_head`` field and a trailing newline the
driver's writer doesn't emit, so the driver's byte-for-byte rewrite of
the SAME passing dryrun registered as a diff.  This writer emits exactly
the driver's format — ``json.dumps({n_devices, rc, ok, skipped, tail},
indent=2)``, ascii-escaped, NO trailing newline — and banks provenance
in a ``<artifact>.head`` sidecar the driver never touches.

Usage::

    python tools/record_multichip.py --out MULTICHIP_r06.json [--n 8]

The byte format is pinned by ``tests/test_bench_legs.py`` against the
committed ``MULTICHIP_r05.json`` (itself a driver rewrite).
"""

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def render_artifact(n_devices: int, rc: int, tail: str,
                    skipped: bool = False) -> str:
    """The driver's exact serialization: key order, indent=2, ascii
    escapes, no trailing newline, no provenance fields."""
    return json.dumps({"n_devices": n_devices, "rc": rc, "ok": rc == 0,
                       "skipped": skipped, "tail": tail}, indent=2)


def run_dryrun(n_devices: int, timeout: int = 1800):
    """``dryrun_multichip(n)`` in a fresh CPU-forced subprocess;
    returns (rc, combined output)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        p = subprocess.run(
            [sys.executable, "-c",
             f"import __graft_entry__ as g; g.dryrun_multichip({n_devices})"],
            cwd=str(REPO), env=env, capture_output=True, text=True,
            timeout=timeout)
    except subprocess.TimeoutExpired as e:
        # a hung dryrun must still produce an ok:false artifact — an
        # unhandled crash here is exactly the unrecorded-run failure
        # mode this tool exists to eliminate
        out = (e.stdout.decode() if isinstance(e.stdout, bytes)
               else e.stdout) or ""
        return 124, out + f"\n--- timed out after {timeout}s ---"
    out = p.stdout
    if p.returncode != 0 and p.stderr:
        out += ("\n--- stderr tail ---\n" + p.stderr[-2000:])
    return p.returncode, out


def git_head() -> str:
    p = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                       cwd=str(REPO), capture_output=True, text=True)
    return p.stdout.strip()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True,
                    help="artifact path, e.g. MULTICHIP_r06.json")
    ap.add_argument("--n", type=int, default=8)
    args = ap.parse_args()

    rc, tail = run_dryrun(args.n)
    out_path = REPO / args.out
    out_path.write_text(render_artifact(args.n, rc, tail))
    # provenance rides in a sidecar the driver's rewrite never touches,
    # so the artifact itself stays byte-stable across re-runs
    head = git_head()
    if head:
        out_path.with_suffix(out_path.suffix + ".head").write_text(
            head + "\n")
    print(f"record_multichip: wrote {out_path.name} "
          f"(rc={rc}, ok={rc == 0}, head={head or '?'})")
    return 0 if rc == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
