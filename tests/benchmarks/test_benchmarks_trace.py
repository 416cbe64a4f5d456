"""The benchmark's trace reduction, on hand-made intervals and on the HLO
texts a v5e trace really holds."""

import json
import os

import pytest

from benchmarks import trace

HERE = os.path.dirname(os.path.abspath(__file__))
NAMES = os.path.join(os.path.dirname(HERE), "data",
                     "tpu_v5e_bsp4_trace_names.json")


def test_union_merges_overlapping_and_touching_intervals():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == \
        [(0, 4), (5, 7)]
    assert trace.total(trace.union([(0, 10), (2, 3)])) == 10


def test_busy_and_idle_share_clip_to_the_window():
    ops = [("a", -5, 10), ("b", 20, 30), ("c", 25, 40), ("d", 95, 120)]
    window = (0, 100)
    assert trace.busy_ns(ops, window) == 10 + 20 + 5
    assert trace.idle_share(ops, window) == pytest.approx(0.65)


def test_gaps_are_the_complement_of_busy_in_the_window():
    busy = trace.union([(10, 20), (30, 40)])
    assert trace.gaps(busy, (0, 50)) == [(0, 10), (20, 30), (40, 50)]
    assert trace.gaps(busy, (12, 35)) == [(20, 30)]
    assert trace.gaps([], (0, 5)) == [(0, 5)]


def test_overlap_of_two_merged_lists():
    a = [(0, 10), (20, 30)]
    b = [(5, 25), (28, 40)]
    assert trace.overlap(a, b) == 5 + 5 + 2


@pytest.mark.parametrize("text,expected", [
    ("%convert_element_type.205 = bf16[256]{0:T(256)(128)(2,1)} "
     "convert(f32[256]{0:T(256)} %bitcast.501)", "convert"),
    ("%all-reduce.16 = (f32[96]{0:T(128)S(1)}, f32[11,11,3,96]{3,2,1,0:"
     "T(4,128)S(1)}, /*index=5*/f32[3,3,256,384]{3,2,1,0}) "
     "all-reduce(f32[96]{0} %a, f32[11,11,3,96]{3,2,1,0} %b), "
     "replica_groups={{0,1,2,3}}", "all-reduce"),
    ("%copy-done.42 = bf16[5,5,48,2,128]{4,3,2,1,0:T(2,128)(2,1)} "
     "copy-done((bf16[5,5,48,2,128]{4,3,2,1,0}, u32[]{:S(2)}) "
     "%copy-start.42)", "copy-done"),
    ("%broadcast_add_fusion.15 = u32[128]{0:T(128)} fusion(), kind=kLoop, "
     "calls=%fused_computation.831", "fusion"),
    ("%ars = ((f32[8]{0}), f32[8]{0}, u32[]{:S(2)}) all-reduce-start("
     "f32[8]{0} %x)", "all-reduce-start"),
    ("%t = ((f32[2]{0}, (s32[]{:T(1)})), f32[2]{0}) tuple-op(%x)",
     "tuple-op"),
    ("jit_per_worker(12109795050817197960)",
     "jit_per_worker(12109795050817197960)"),
])
def test_opcode_is_the_token_after_the_result_type(text, expected):
    assert trace.opcode(text) == expected


def test_opcode_on_every_example_of_the_recorded_v5e_names():
    with open(NAMES) as f:
        by_opcode = json.load(f)["lines"]["XLA Ops"]["by_opcode"]
    assert "all-reduce" in by_opcode and len(by_opcode) > 10
    for op, entry in by_opcode.items():
        # examples are cut to 200 characters: the opcode must be found
        # even where the cut falls inside a tuple's result type
        if entry["example"].count("(") and op != "all-reduce":
            assert trace.opcode(entry["example"]) == op, entry["example"]
    assert trace.instruction_name(by_opcode["all-reduce"]["example"]) == \
        "%all-reduce.16"


def test_collective_base_knows_the_async_forms():
    assert trace.collective_base("all-reduce") == "all-reduce"
    assert trace.collective_base("all-gather-start") == "all-gather"
    assert trace.collective_base("collective-permute-done") == \
        "collective-permute"
    assert trace.collective_base("fusion") is None
    assert trace.collective_base("copy-start") is None


def _op(name, opcode, start, end, operands=""):
    return (f"%{name} = f32[8]{{0}} {opcode}({operands})", start, end)


def test_sync_collective_is_wholly_exposed():
    ops = [_op("f.1", "fusion", 0, 10), _op("ar.1", "all-reduce", 10, 30),
           _op("f.2", "fusion", 30, 40)]
    assert trace.collective_ns(ops) == 20
    assert trace.exposed_collective_ns(ops) == 20


def test_async_pair_counts_start_to_done_and_overlap_hides_it():
    ops = [_op("ars.1", "all-reduce-start", 0, 2, "f32[8]{0} %g"),
           _op("f.1", "fusion", 2, 50),
           _op("ard.1", "all-reduce-done", 50, 60, "f32[8]{0} %ars.1")]
    assert trace.collective_intervals(ops) == [(0, 60)]
    assert trace.collective_ns(ops) == 60
    assert trace.exposed_collective_ns(ops) == 12     # 0-2 and 50-60


def test_async_pairs_match_by_operand_not_by_order():
    ops = [_op("ags.1", "all-gather-start", 0, 1, "%a"),
           _op("ags.2", "all-gather-start", 1, 2, "%b"),
           _op("agd.2", "all-gather-done", 10, 11, "f32[8]{0} %ags.2"),
           _op("agd.1", "all-gather-done", 20, 21, "f32[8]{0} %ags.1")]
    assert sorted(trace.collective_intervals(ops)) == [(0, 21), (1, 11)]
    assert trace.collective_ns(ops) == 21


def test_unpaired_start_and_done_keep_their_own_events():
    ops = [_op("ard.9", "all-reduce-done", 5, 9, "%ars.9"),
           _op("ars.3", "all-reduce-start", 20, 22, "%g")]
    assert sorted(trace.collective_intervals(ops)) == [(5, 9), (20, 22)]


def _tables():
    modules = [("jit_per_worker(1)", 100, 200), ("jit__mean(2)", 205, 206),
               ("jit_per_worker(1)", 300, 400), ("jit_per_worker(1)", 480, 520)]
    ops = [_op("f.1", "fusion", 100, 150), _op("ar.1", "all-reduce", 150, 190),
           _op("m.1", "reduce", 205, 206),
           _op("f.1", "fusion", 300, 350), _op("ar.1", "all-reduce", 350, 390),
           _op("f.1", "fusion", 480, 520)]
    return trace.TraceTables([trace.DeviceTables(0, modules, ops)])


WINDOW = (50, 500)


def test_steps_are_the_train_programs_executions_wholly_in_the_window():
    t = _tables()
    assert trace.train_program(t.devices[0].modules) == "jit_per_worker"
    assert trace.step_intervals(t.devices[0], WINDOW) == \
        [(100, 200), (300, 400)]
    assert trace.mean_step_ns(t, WINDOW) == 100
    steps, ops = trace.steps_and_ops(t, WINDOW)
    assert len(ops) == 4 and trace.collective_ns(ops) == 80


def test_no_window_or_no_trace_means_nothing_to_read():
    assert trace.mean_step_ns(_tables(), None) is None
    assert trace.steps_and_ops(_tables(), None) == ([], [])
    assert trace.mean_step_ns(None, WINDOW) is None
    assert trace.mean_step_ns(trace.TraceTables([]), WINDOW) is None


def test_top_device_ops_group_by_opcode_and_instruction():
    t = _tables()
    top = trace.top_device_ops(t.devices[0].ops, WINDOW, n=2)
    assert top[0] == ["fusion %f.1", pytest.approx(120e-9)]
    assert top[1] == ["all-reduce %ar.1", pytest.approx(80e-9)]


def test_idle_time_goes_to_the_innermost_host_interval():
    idle = [(0, 100), (200, 260)]
    host = [("train_iter", 0, 90), ("load", 10, 60), ("print_train_info",
                                                      210, 250)]
    got = dict(map(tuple, trace.attribute_gaps(idle, host)))
    assert got == {"load": pytest.approx(50e-9),
                   "train_iter": pytest.approx(40e-9),
                   "print_train_info": pytest.approx(40e-9),
                   "between_calls": pytest.approx(30e-9)}


def test_text_proto_round_trip_through_profile_data(tmp_path):
    text = '''
planes { name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 } }
  lines { name: "Async XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_per_worker(7)" } }
  event_metadata { key: 2 value { id: 2
    name: "%ar.1 = (f32[2]{0}, f32[3]{0}) all-reduce(%a, %b)" } } }
planes { name: "/device:TPU:1" }
planes { name: "#Chip0 Misc" }
planes { name: "Task Environment"
  stat_metadata { key: 1 value { id: 1 name: "profile_start_time" } }
  stat_metadata { key: 2 value { id: 2 name: "profile_stop_time" } }
  stats { metadata_id: 1 uint64_value: 1790446297839978589 }
  stats { metadata_id: 2 uint64_value: 1790446298195765031 } }
'''
    p = tmp_path / "t.pbtxt"
    p.write_text(text)
    t = trace.load_text_proto(str(p))
    assert [d.index for d in t.devices] == [0, 1]
    assert t.start_unix_ns == 1790446297839978589
    assert t.on_trace_clock(1790446297839978589 + 2500) == 2500
    dev = t.devices[0]
    assert dev.modules == [("jit_per_worker(7)", 1000, 6000)]
    assert trace.opcode(dev.ops[0][0]) == "all-reduce"
    assert dev.ops[0][1:] == (2000, 4000)


# -- scopes: op_name paths as jax 0.9.0 writes them --------------------------------

FWD = "jit(per_worker)/jvp(block0)/mla/dot_general"
BWD = "jit(per_worker)/transpose(jvp(block0))/mla/dot_general"
REMAT = "jit(step)/vmap(transpose(jvp(head)))/vmap(jvp(head))/checkpoint/cos"
UPDATE = "jit(per_worker)/mul"


def test_path_parts_cut_outside_parentheses_and_strip_wrappers():
    assert trace.path_parts(BWD) == [
        (("jit",), "per_worker"), (("transpose", "jvp"), "block0"),
        ((), "mla"), ((), "dot_general")]
    assert trace.path_parts(REMAT)[1] == (("vmap", "transpose", "jvp"),
                                          "head")
    # an unscoped primitive under a transform: jax writes an empty name
    assert trace.path_parts("jit(f)/jvp()/conv_general_dilated")[1] == \
        (("jvp",), "")
    # a `/` inside parentheses does not cut
    assert [p for _, p in trace.path_parts("jit(f)/jvp(a/b)/c")] == \
        ["f", "a/b", "c"]


@pytest.mark.parametrize("op_name,way", [
    (FWD, "forward"), (BWD, "backward"), (REMAT, "backward"),
    (UPDATE, "other"), ("state['params']['fc6']['w']", "other"),
    ("jit(f)/jvp()/conv_general_dilated", "forward"),
    ("jit(f)/transpose(jvp())/conv_general_dilated", "backward")])
def test_direction_reads_the_transforms(op_name, way):
    assert trace.direction(op_name) == way


def test_in_scope_matches_a_whole_part_in_either_direction():
    for name in (FWD, BWD):
        assert trace.in_scope(name, "mla") and trace.in_scope(name, "block0")
        assert not trace.in_scope(name, "ml")
        assert not trace.in_scope(name, "block")
    assert trace.in_scope(FWD, "mla", "forward")
    assert not trace.in_scope(FWD, "mla", "backward")
    assert trace.in_scope(BWD, "mla", "backward")
    assert not trace.in_scope(UPDATE, "mla")
    assert not trace.in_scope("", "mla")


def test_scope_tail_keeps_the_direction_and_the_primitive():
    assert trace.scope_tail(BWD, 64) == \
        "transpose(jvp(block0))/mla/dot_general"
    assert trace.scope_tail("jit(a)/jit(main)/mul", 64) == "mul"
    # on a mesh every path runs through shard_map: it goes with the jits
    assert trace.scope_tail(
        "jit(per_worker)/shard_map/transpose(jvp())/select_and_scatter_add",
        23) == "transpose(jvp())/select"
    assert trace.scope_tail("jit(per_worker)/shard_map", 64) == "shard_map"
    long = "jit(f)/transpose(jvp(block0))/" + "/".join(
        f"scope_number_{i}" for i in range(8)) + "/dot_general"
    cut = trace.scope_tail(long, 48)
    assert len(cut) <= 48
    assert cut.startswith("transpose(jvp(block0))/../")
    assert cut.endswith("/dot_general")
    assert len(trace.scope_tail("x" * 100, 20)) == 20


def test_scope_busy_is_a_union_and_not_a_sum():
    scopes = {"a.1": FWD, "a.2": BWD, "u.1": UPDATE}
    ops = [("%a.1 = f32[] fusion(...)", 0, 10),
           ("%a.2 = f32[] fusion(...)", 5, 20),      # overlaps a.1
           ("%u.1 = f32[] fusion(...)", 15, 40),
           ("%x.9 = f32[] copy-start(...)", 40, 50)]  # not in the text
    w = (0, 100)
    assert trace.scope_busy_ns(ops, w, scopes, "mla") == 20      # not 25
    assert trace.scope_busy_ns(ops, w, scopes, "mla", "forward") == 10
    assert trace.scope_busy_ns(ops, w, scopes, "mla", "backward") == 15
    assert trace.scope_busy_ns(ops, (8, 12), scopes, "mla") == 4
    assert trace.scope_busy_ns(ops, w, scopes, "per_worker") == 40
    # busy 50, of which only x.9's 10 ran with no scoped instruction
    assert trace.unscoped_share(ops, w, scopes) == pytest.approx(0.2)


def test_scopes_from_hlo_on_a_cpu_style_text():
    text = """
HloModule jit_f

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %t = f32[4]{0} tanh(%p), metadata={op_name="jit(f)/jvp(mla)/tanh" stack_frame_id=3}
  ROOT %c = f32[4]{0} convert(%t)
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), metadata={op_name="w[\\'a\\']"}
  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  ROOT %copy.2 = f32[4]{0} copy(%fusion.1)
}
"""
    scopes = trace.scopes_from_hlo(text)
    assert scopes == {"t": "jit(f)/jvp(mla)/tanh",
                      "fusion.1": "jit(f)/jvp(mla)/tanh", "x": "w['a']"}
    assert trace.scopes_from_hlo("") == {}
