"""Record the two small device traces kept under benchmark/fixtures/ for
the tests of the `device_scope` reader, on the chip: the toy models of
`record_fixture.py` (two train steps of a two-layer GPT with the flash
kernels) and `record_serve_fixture.py` (a two-layer engine at GPT-3
125M's width serving eight requests), recorded by those recorders'
own `main`, from a program that opens `telemetry.scope`s, so that the
ops' metadata carries `pt.<layer>` as the chip really writes it.

    python3 benchmark/tools/record_scoped_fixtures.py [out dir]

writes `v5e_scoped_train.xplane.pb.gz` and `v5e_scoped_serve.xplane.pb.gz`
(under chiprun_out/ by default; copy them to benchmark/fixtures/) and
prints `scope_report` of each.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

LIMIT = 512 * 1024      # bytes a fixture may take in the repository


def main(out_dir):
    from benchmark.tools import (record_fixture, record_serve_fixture,
                                 scope_report)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    os.makedirs(out_dir, exist_ok=True)
    for name, record in (("v5e_scoped_train", record_fixture.main),
                         ("v5e_scoped_serve", record_serve_fixture.main)):
        out = os.path.join(out_dir, name + ".xplane.pb.gz")
        record(out)
        size = os.path.getsize(out)
        if size >= LIMIT:
            raise SystemExit(f"record_scoped_fixtures: {out} has {size} "
                             f"bytes, over {LIMIT}")
        found = scope_report.load(out)
        if not any(op.owner == "attn" for op in found.ops):
            raise SystemExit(f"record_scoped_fixtures: no op of {out} "
                             "bears pt.attn")
        scope_report.report(found, rows=6)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1
         else os.path.join(ROOT, "chiprun_out", "fixtures"))
