"""HLO cost profiler: per-opcode / per-op breakdown of the roofline terms.

This is the tool behind every EXPERIMENTS.md §Perf iteration — it answers
"which op class owns the dominant term?" for a compiled (arch x shape).

  PYTHONPATH=src python -m repro.launch.profile --arch qwen2-7b \
      --shape train_4k --top 20

Like the dry-run, ``main()`` forces 512 host devices before JAX starts;
importing this module changes nothing.
"""
import argparse
from collections import defaultdict

from repro.launch import roofline as rl


def profile_hlo(text: str):
    """-> (per-opcode byte totals, top single ops, collective breakdown)."""
    comps, entry = rl.parse_hlo(text)
    by_op: dict = defaultdict(float)
    tops: list = []
    colls: dict = defaultdict(float)

    def walk(name, mult, count_bytes=True):
        comp = comps.get(name)
        if comp is None:
            return
        for op in comp.ops:
            oc = op.opcode
            if oc == "while":
                trip = rl._trip_count(op)
                for b in rl._called(op, "body") + rl._called(op, "condition"):
                    walk(b, mult * trip, count_bytes)
            elif oc == "fusion":
                if count_bytes:
                    nb = mult * rl._fusion_bytes(op, comp, comps)
                    by_op["fusion"] += nb
                    tops.append((nb, "fusion", op.name, op.type_str[:60]))
                for c in rl._called(op, "calls"):
                    walk(c, mult, False)
            elif oc in ("call",):
                for c in rl._called(op, "to_apply") + rl._called(op, "calls"):
                    walk(c, mult, count_bytes)
            else:
                if any(oc.startswith(c) for c in rl.COLLECTIVES):
                    nb = sum(rl._type_bytes(comp.by_name[o].type_str)
                             for o in rl._operand_names(op)
                             if o in comp.by_name) or rl._type_bytes(op.type_str)
                    colls[oc] += mult * nb
                if count_bytes and oc not in (
                        "parameter", "constant", "get-tuple-element",
                        "tuple", "bitcast"):
                    nb = mult * rl._op_bytes(op, comp)
                    by_op[oc] += nb
                    tops.append((nb, oc, op.name, op.type_str[:60]))

    walk(entry, 1.0)
    tops.sort(reverse=True)
    return dict(by_op), tops, dict(colls)


def main(argv=None):
    from repro.launch.dryrun import build_lowered, force_host_devices
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--hlo-out", default=None)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    lowered, meta = build_lowered(args.arch, args.shape,
                                  multi_pod=args.multi_pod)
    txt = lowered.compile().as_text()
    if args.hlo_out:
        open(args.hlo_out, "w").write(txt)

    by_op, tops, colls = profile_hlo(txt)
    total = sum(by_op.values())
    print(f"== {args.arch} x {args.shape} mesh={meta['mesh']} — "
          f"bytes/device {total:.3e} ==")
    print("\nper-opcode bytes:")
    for k, v in sorted(by_op.items(), key=lambda x: -x[1])[:12]:
        print(f"  {k:22s} {v:11.3e}  ({v/total:6.1%})")
    if colls:
        print("\ncollective bytes:")
        for k, v in sorted(colls.items(), key=lambda x: -x[1]):
            print(f"  {k:22s} {v:11.3e}")
    print(f"\ntop {args.top} single ops (x trip count):")
    for nb, oc, name, t in tops[:args.top]:
        print(f"  {nb:10.3e} {oc:14s} {name[:40]:40s} {t}")


if __name__ == "__main__":
    main()
