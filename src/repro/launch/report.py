"""Generate the EXPERIMENTS.md §Dry-run/§Roofline tables from the JSONs.

  PYTHONPATH=src python -m repro.launch.report > experiments/roofline.md
"""

from __future__ import annotations

import glob
import json
import os
import subprocess

from repro.configs import CELLS

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")
REPO_ROOT = os.path.normpath(os.path.join(OUT_DIR, "..", ".."))


def load_all():
    rows = {}
    for f in sorted(glob.glob(os.path.join(OUT_DIR, "*.json"))):
        d = json.load(open(f))
        tag = d.get("meta", {}).get("overrides", {}).get("tag", "")
        rows[(d["mesh"], d["arch"], d["shape"], tag)] = d
    return rows


def _committed(fname: str):
    """The HEAD-committed version of a dry-run JSON (None if new/no git)."""
    rel = os.path.relpath(os.path.abspath(fname), REPO_ROOT)
    try:
        out = subprocess.run(["git", "show", f"HEAD:{rel}"],
                             capture_output=True, cwd=REPO_ROOT, timeout=30)
        if out.returncode != 0:
            return None
        return json.loads(out.stdout)
    except (OSError, ValueError, subprocess.SubprocessError):
        return None


def mem_deltas():
    """(key, old GiB/dev, new GiB/dev, old fit, new fit) for every
    dry-run JSON whose memory footprint changed vs the committed
    snapshot — the fit-regression signal a PR diff should surface.
    A cell with no committed counterpart (e.g. a freshly added
    S_v × S_w factorization) is included with ``old = None``: a new
    cell's footprint and fit verdict belong in the PR surface too,
    they just have no delta."""
    deltas = []
    for f in sorted(glob.glob(os.path.join(OUT_DIR, "*.json"))):
        new = json.load(open(f))
        old = _committed(f)
        gib = lambda d: d["memory_analysis"]["total_nonalias_bytes"] / 2**30
        if old is None:
            deltas.append(((new["mesh"], new["arch"], new["shape"]),
                           None, gib(new), None, new["hbm_fit"]))
            continue
        if abs(gib(new) - gib(old)) < 1e-3 and new["hbm_fit"] == old["hbm_fit"]:
            continue
        deltas.append(((new["mesh"], new["arch"], new["shape"]),
                       gib(old), gib(new), old["hbm_fit"], new["hbm_fit"]))
    return deltas


def fmt_row(d) -> str:
    tc, tm, tx = d["t_compute"], d["t_memory"], d["t_collective"]
    dom = max(tc, tm, tx)
    frac = tc / max(dom, 1e-12)       # compute / dominant term
    mem_gib = d["memory_analysis"]["total_nonalias_bytes"] / 2**30
    return (f"| {d['arch']} | {d['shape']} | {d['mesh']} "
            f"| {d['flops_per_device']:.2e} | {d['bytes_per_device']:.2e} "
            f"| {d['coll_bytes_per_device']:.2e} "
            f"| {tc * 1e3:.1f} | {tm * 1e3:.1f} | {tx * 1e3:.1f} "
            f"| {d['bottleneck']} | {frac:.2f} | {d['useful_ratio']:.2f} "
            f"| {mem_gib:.2f} | {'Y' if d['hbm_fit'] else 'N'} |")


HEADER = ("| arch | shape | mesh | FLOPs/dev | bytes/dev | coll B/dev "
          "| t_comp ms | t_mem ms | t_coll ms | bottleneck "
          "| roofline-frac | useful | GiB/dev | fit |")
SEP = "|" + "---|" * 14


def main():
    rows = load_all()
    print("## Roofline table (generated by repro.launch.report)\n")
    print(HEADER)
    print(SEP)
    for key in sorted(rows):
        if key[3]:
            continue                    # perf-iteration tags listed after
        print(fmt_row(rows[key]))
    skips = [(a, c["shape"].name, c["reason"])
             for a, cs in CELLS.items() for c in cs if c["skip"]]
    print("\n### Skipped cells (DESIGN.md §4 policy)\n")
    for a, s, r in skips:
        print(f"- {a} × {s}: {r}")
    tagged = [(k, v) for k, v in rows.items() if k[3]]
    if tagged:
        print("\n### Perf-iteration variants\n")
        print(HEADER)
        print(SEP)
        for k, v in sorted(tagged):
            print(fmt_row(v).replace(f"| {v['shape']} ",
                                     f"| {v['shape']}[{k[3]}] "))
    deltas = mem_deltas()
    if deltas:
        print("\n### GiB/dev deltas vs committed snapshots (HEAD)\n")
        print("| mesh | arch | shape | GiB/dev HEAD | GiB/dev now "
              "| delta | fit HEAD→now |")
        print("|" + "---|" * 7)
        for (mesh, arch, shape), g0, g1, f0, f1 in deltas:
            if g0 is None:
                print(f"| {mesh} | {arch} | {shape} | new | {g1:.2f} "
                      f"| — | —→{'Y' if f1 else 'N'} |")
            else:
                print(f"| {mesh} | {arch} | {shape} | {g0:.2f} | {g1:.2f} "
                      f"| {g1 - g0:+.2f} "
                      f"| {'Y' if f0 else 'N'}→{'Y' if f1 else 'N'} |")


if __name__ == "__main__":
    main()
