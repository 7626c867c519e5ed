"""The `device_scope` reader and the wire decoder under it
(benchmark/xplane_meta.py): against the generated `xplane_pb2` on the
recorded traces (where it imports), on hand-written planes whose
numbers can be worked out on paper, on the two scope-less fixtures of
earlier PRs (a program that opens no scopes reads None, never 0) and on
the two recorded from a program with `telemetry.scope`
(tools/record_scoped_fixtures.py, on the v5e)."""
import gzip
import os

import pytest

from benchmark import harness, trace_reduce, xplane_meta
from benchmark.readers import device_scope as ds

US = 1_000_000      # picoseconds in a microsecond
BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
ROWS = [r for r in BENCH["per_layer"] if harness.load_json(
    harness.HERE, "metrics", r["name"] + ".json")["reader"] == "device_scope"]
FIXTURES = os.path.join(harness.HERE, "fixtures")
OLD = ("v5e_train_steps", "v5e_serve_steps")
SCOPED = ("v5e_scoped_train", "v5e_scoped_serve")
SERVING = [w["name"] for w in BENCH["workloads"] if w["traffic"] != "train"]


def _bytes(name):
    path = os.path.join(FIXTURES, name + ".xplane.pb.gz")
    if not os.path.exists(path):
        pytest.skip(f"no {name} in the fixtures")
    with gzip.open(path) as f:
        return f.read()


def _args(metric):
    return harness.load_json(harness.HERE, "metrics", metric + ".json")["args"]


# -- the wire decoder ---------------------------------------------------------

@pytest.mark.parametrize("name", OLD + SCOPED)
def test_decoder_agrees_with_xplane_pb2(name):
    pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    data = _bytes(name)
    space = pb2.XSpace()
    space.ParseFromString(data)
    mine = xplane_meta.planes(data)
    assert [p.name for p in mine] == [p.name for p in space.planes]
    for got, want in zip(mine, space.planes):
        stat_names = {k: v.name for k, v in want.stat_metadata.items()}
        assert set(got.metadata) == set(want.event_metadata)
        for mid, meta in want.event_metadata.items():
            name_, stats = got.metadata[mid]
            assert name_ == meta.name
            assert set(stats) == {stat_names[s.metadata_id]
                                  for s in meta.stats}
            for s in meta.stats:
                kind = s.WhichOneof("value")
                value = stat_names[s.ref_value] if kind == "ref_value" \
                    else getattr(s, kind)
                assert stats[stat_names[s.metadata_id]] == value
        assert list(got.lines) == list(dict.fromkeys(
            ln.name for ln in want.lines))
        for line in want.lines:
            events = [(e.metadata_id,
                       line.timestamp_ns * 1000 + e.offset_ps,
                       line.timestamp_ns * 1000 + e.offset_ps
                       + e.duration_ps) for e in line.events]
            if len([ln for ln in want.lines if ln.name == line.name]) == 1:
                assert got.lines[line.name] == events


def test_decoder_reads_every_kind_of_stat_and_skips_what_it_is_not_asked():
    from jax.profiler import ProfileData
    text = '''planes { name: "/device:TPU:0"
      lines { name: "XLA Ops" timestamp_ns: 7
        events { metadata_id: 1 offset_ps: 500 duration_ps: 250
                 stats { metadata_id: 1 int64_value: 3 } } }
      lines { name: "Steps" events { metadata_id: 1 offset_ps: 1 } }
      event_metadata { key: 1 value { id: 1 name: "%a = f32[] add()"
        stats { metadata_id: 1 str_value: "jit(f)/pt.mlp/add:" }
        stats { metadata_id: 2 ref_value: 3 }
        stats { metadata_id: 4 int64_value: -5 }
        stats { metadata_id: 5 uint64_value: 18446744073709551615 }
        stats { metadata_id: 6 double_value: 0.5 }
        stats { metadata_id: 7 bytes_value: "\\001\\002" } } }
      stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
      stat_metadata { key: 2 value { id: 2 name: "hlo_category" } }
      stat_metadata { key: 3 value { id: 3 name: "loop fusion" } }
      stat_metadata { key: 4 value { id: 4 name: "i" } }
      stat_metadata { key: 5 value { id: 5 name: "u" } }
      stat_metadata { key: 6 value { id: 6 name: "d" } }
      stat_metadata { key: 7 value { id: 7 name: "b" } } }
    planes { name: "/host:CPU" lines { name: "t" } }'''
    data = ProfileData.text_proto_to_serialized_xspace(text)
    (plane,) = xplane_meta.planes(
        data, lambda n: n.startswith("/device:"), ("XLA Ops",))
    assert plane.lines == {"XLA Ops": [(1, 7_500, 7_750)]}
    assert plane.metadata[1] == ("%a = f32[] add()", {
        "tf_op": "jit(f)/pt.mlp/add:", "hlo_category": "loop fusion",
        "i": -5, "u": 2 ** 64 - 1, "d": 0.5, "b": b"\x01\x02"})
    assert [p.name for p in xplane_meta.planes(data)] == \
        ["/device:TPU:0", "/host:CPU"]


# -- hand-written planes ------------------------------------------------------

def _plane(ops, modules=()):
    """ops: [(HLO text, tf_op or None, start_us, dur_us, program id)];
    modules: [(name, start_us, dur_us)] -> a serialized XSpace."""
    from jax.profiler import ProfileData
    meta, events, mods = {}, [], []
    for text, tf_op, start, dur, prog in ops:
        mid = meta.setdefault((text, tf_op, prog), len(meta) + 1)
        events.append(f"events {{ metadata_id: {mid} offset_ps: "
                      f"{start * US} duration_ps: {dur * US} }}")
    body = []
    for (text, tf_op, prog), mid in meta.items():
        stats = f"stats {{ metadata_id: 2 uint64_value: {prog} }}"
        if tf_op is not None:
            stats += f' stats {{ metadata_id: 1 str_value: "{tf_op}" }}'
        body.append(f'event_metadata {{ key: {mid} value {{ id: {mid} '
                    f'name: "{text}" {stats} }} }}')
    for name, start, dur in modules:
        mid = len(meta) + len(mods) + 1
        mods.append(f"events {{ metadata_id: {mid} offset_ps: {start * US} "
                    f"duration_ps: {dur * US} }}")
        body.append(f'event_metadata {{ key: {mid} value {{ id: {mid} '
                    f'name: "{name}" }} }}')
    return ProfileData.text_proto_to_serialized_xspace(
        'planes { name: "/device:TPU:0" lines { name: "XLA Ops" '
        f'{" ".join(events)} }} lines {{ name: "XLA Modules" '
        f'{" ".join(mods)} }} {" ".join(body)} '
        'stat_metadata { key: 1 value { id: 1 name: "tf_op" } } '
        'stat_metadata { key: 2 value { id: 2 name: "program_id" } } }')


def _run(data):
    (plane,) = xplane_meta.planes(data, lambda n: n.startswith("/device:"))
    return {ds.KEY: ds.from_plane(plane)}


def _share(run, scope):
    return ds.read({"mode": "scope", "scope": scope}, run)


@pytest.fixture(scope="module")
def by_hand():
    """One device, microseconds, 1,000 busy:
      0-100   fusion.1        jit(decode_fn)/pt.attn/dot_general
    100-200   fusion.2        .../pt.experts/pt.mlp/mul   (innermost: mlp)
    200-300   fusion.3        .../transpose(jvp(pt.mlp))/mul
    300-400   copy-start.3    .../pt.experts/gather    (its end at 700)
    400-500   gather.7        jit(decode_fn)/jit(_take)/gather   (no scope)
    500-600   copy.11         param_vals[21]           (a name, no scope)
    600-700   copy.5          no tf_op
    700-800   copy-done.3     no tf_op, ends copy-start.3 -> experts
    800-900   slice-done.1    no tf_op, ends slice-start.1 of ANOTHER
                              program, which has no name either
    900-1100  while.2         .../pt.ssm/while, 950-1050 of it under
    950-1050  fusion.9        .../pt.ssm/pt.attn/add
    idle 1100-1200, then 1200-1300 sort.4 .../pt.sample/sort"""
    f = "jit(decode_fn)"
    return _run(_plane([
        ("%fusion.1 = f32[8] fusion(%p)", f + "/pt.attn/dot_general:", 0,
         100, 1),
        ("%fusion.2 = f32[8] fusion(%p)", f + "/pt.experts/pt.mlp/mul:",
         100, 100, 1),
        ("%fusion.3 = f32[8] fusion(%p)", f + "/transpose(jvp(pt.mlp))/mul:",
         200, 100, 1),
        ("%copy-start.3 = (f32[8], f32[8], u32[]) copy-start(f32[8] %x)",
         f + "/pt.experts/gather:", 300, 100, 1),
        ("%gather.7 = f32[8] gather(%t)", f + "/jit(_take)/gather:", 400,
         100, 1),
        ("%copy.11 = s8[8] copy(%param_vals_21)", "param_vals[21]:", 500,
         100, 1),
        ("%copy.5 = f32[8] copy(%y)", None, 600, 100, 1),
        ("%copy-done.3 = f32[8] copy-done((f32[8], f32[8], u32[]) "
         "%copy-start.3)", None, 700, 100, 1),
        ("%slice-done.1 = f32[8] async-done((f32[8]) %slice-start.1)", None,
         800, 100, 2),
        ("%while.2 = f32[8] while(%z)", f + "/pt.ssm/while:", 900, 200, 1),
        ("%fusion.9 = f32[8] fusion(%w)", f + "/pt.ssm/pt.attn/add:", 950,
         100, 1),
        ("%sort.4 = f32[8] sort(%l)", f + "/pt.sample/sort:", 1200, 100,
         1)],
        modules=[("jit_decode_fn(1)", 0, 1150), ("jit_prefill_fn(3)", 1200,
                                                 100)]))


def test_the_innermost_scope_owns_the_op(by_hand):
    assert _share(by_hand, "attn") == pytest.approx(100 * 200 / 1200)
    assert _share(by_hand, "mlp") == pytest.approx(100 * 200 / 1200)
    assert _share(by_hand, "sample") == pytest.approx(100 * 100 / 1200)
    # an op that holds another keeps what the other leaves of it
    assert _share(by_hand, "ssm") == pytest.approx(100 * 100 / 1200)
    assert _share(by_hand, "head") is None      # never 0
    assert ds.innermost("jit(f)/jit(pt_like)/mul:") is None


def test_a_done_takes_the_scope_of_its_start(by_hand):
    # copy-start.3 and copy-done.3; slice-done.1 has no named start
    assert _share(by_hand, "experts") == pytest.approx(100 * 200 / 1200)
    assert ds.read({"mode": "xla_own"}, by_hand) == \
        pytest.approx(100 * 200 / 1200)


def test_unscoped_is_a_name_without_a_layer(by_hand):
    assert ds.read({"mode": "unscoped"}, by_hand) == \
        pytest.approx(100 * 200 / 1200)
    names = {op.name: op.owner for op in by_hand[ds.KEY].ops}
    assert names["gather.7"] == names["copy.11"] == "unscoped"
    assert names["copy.5"] == names["slice-done.1"] == "xla_own"


def test_the_owners_add_up_to_the_busy_time(by_hand):
    scoped = by_hand[ds.KEY]
    assert scoped.busy_ps == 1200 * US
    total = sum(_share(by_hand, s) for s in scoped.owners()
                if s not in (ds.UNSCOPED, ds.XLA_OWN))
    total += ds.read({"mode": "unscoped"}, by_hand)
    total += ds.read({"mode": "xla_own"}, by_hand)
    assert total == pytest.approx(100.0)


def test_programs_by_name(by_hand):
    prefill = _args("device_share.prefill_program")
    assert ds.read(prefill, by_hand) == pytest.approx(100 * 100 / 1200)
    assert ds.read({"mode": "program", "pattern": "decode_fn"}, by_hand) == \
        pytest.approx(100 * 1150 / 1200)
    assert ds.read({"mode": "program", "pattern": "fork_fn"}, by_hand) \
        is None
    with pytest.raises(ValueError):
        ds.read({"mode": "mean"}, by_hand)


def test_partly_overlapping_ops_share_no_instant():
    acc = ds.self_times([(1, 0, 100), (2, 50, 150), (3, 300, 400),
                         (4, 320, 340), (4, 360, 380)])
    assert acc == {1: 50, 2: 100, 3: 60, 4: 40}


def test_no_device_plane_reads_none():
    assert ds.read({"mode": "xla_own"}, {ds.KEY: None}) is None
    assert ds.read({"mode": "xla_own"}, {}) is None      # no tracer


# -- traces recorded on the chip ----------------------------------------------

def _recorded(name, tmp_path_factory):
    data = _bytes(name)
    out = tmp_path_factory.mktemp(name)
    at = out / "plugins" / "profile" / "v5e"
    at.mkdir(parents=True)
    (at / "t.xplane.pb").write_bytes(data)
    tracer = harness.Tracer(str(out), 0.0, 0.0)
    tracer.state, tracer.t_on, tracer.t_off = "done", 0.0, 0.0
    return {"tracer": tracer,
            "trace": trace_reduce.load(trace_reduce.find_xplane(str(out)))}


@pytest.mark.parametrize("name", OLD)
def test_a_program_without_scopes_reads_none_and_never_zero(
        name, tmp_path_factory):
    run = _recorded(name, tmp_path_factory)
    for row in ROWS:
        args = _args(row["name"])
        value = ds.read(args, run)
        if args["mode"] in ("scope", "unscoped"):
            assert value is None, row["name"]
        else:
            assert value is None or value > 0, row["name"]
    assert ds.scoped_of(run) is run[ds.KEY]         # loaded once
    assert 0 < ds.read({"mode": "xla_own"}, run) < 10
    # PR 36 named the serving programs: before it the decode program
    # was `jit(<unknown>)`
    if name == "v5e_serve_steps":
        assert ds.read(_args("device_share.prefill_program"), run) > 10
        assert any("unknown" in n for n, _ in run[ds.KEY].modules)


@pytest.fixture(scope="module", params=SCOPED)
def scoped(request, tmp_path_factory):
    return request.param, _recorded(request.param, tmp_path_factory)


def test_recorded_shares_add_up(scoped):
    name, run = scoped
    found = ds.scoped_of(run)
    train = name == "v5e_scoped_train"
    want = {"embed", "attn", "mlp", "head"} | (
        {"loss", "optimizer"} if train else {"sample", "cast"})
    assert want <= set(found.owners()) <= want | {ds.UNSCOPED, ds.XLA_OWN}
    total = 0.0
    for row in ROWS:
        if row["name"].endswith(".train") != train \
                or row["name"] == "device_share.prefill_program":
            continue
        value = ds.read(_args(row["name"]), run)
        assert value is None or value > 0
        total += value or 0.0
    # the ops' own union against `trace_reduce.busy_seconds`, which
    # rounds every op's ends to whole nanoseconds
    assert total == pytest.approx(100.0, abs=0.5)
    # nobody's: nothing of a train step; of the toy engine, whose model
    # is two layers, the steps' block-table arithmetic, `fork_fn` and
    # `merge_fn` (0.4% and less in the cells: PERF.md section 5)
    assert ds.read({"mode": "unscoped"}, run) < (1 if train else 8)
    assert _share(run, "attn") > 20


def test_recorded_backward_and_programs_are_named(scoped):
    name, run = scoped
    found = ds.scoped_of(run)
    if name == "v5e_scoped_train":
        back = [op for op in found.ops if "transpose(" in (op.tf_op or "")]
        assert back and all(op.owner in ("attn", "mlp", "head", "loss",
                                         "embed") for op in back)
        flash = [op for op in found.ops if "flash_bwd" in op.name]
        assert flash and all(op.owner == "attn" for op in flash)
        kernels = trace_reduce.kernel_seconds(run["trace"], "flash_(fwd|bwd)")
        assert found.seconds("attn") >= kernels
    else:
        names = {n.split("(")[0] for n, _ in found.modules}
        assert {"jit_decode_greedy_fn", "jit_prefill_fn"} <= names
        assert not [n for n in names if "unknown" in n]
        assert ds.read(_args("device_share.prefill_program"), run) > 10


# -- the rows -----------------------------------------------------------------

def test_metric_files_and_rows_agree():
    """Nineteen rows, one file each; the scope a row reads is one the
    program opens."""
    from paddle_tpu import telemetry
    assert len(ROWS) == 19
    by_scope = {"cast": SERVING[:2], "experts": [SERVING[2], SERVING[4]],
                "ssm": [SERVING[3]]}
    for row in ROWS:
        meta = harness.load_json(harness.HERE, "metrics",
                                 row["name"] + ".json")
        assert set(meta) == {"name", "unit", "layer", "moves", "reader",
                             "args"}
        assert meta["name"] == row["name"] and meta["unit"] == row["unit"] \
            == "%"
        assert meta["layer"] == row["layer"] == "model layers"
        assert row["source"] == "device_trace" and row["better"] == "lower"
        train = row["name"].endswith(".train")
        assert meta["moves"] == row["moves"] == (
            "train_tokens_per_s" if train else "serve_tokens_per_s")
        args = meta["args"]
        if args["mode"] == "scope":
            assert args["scope"] in telemetry.SCOPES
            assert row["name"] == "device_share." + args["scope"] \
                + (".train" if train else "")
        assert row["workloads"] == (
            ["gpt3-125m.train"] if train
            else by_scope.get(args.get("scope"), SERVING))
