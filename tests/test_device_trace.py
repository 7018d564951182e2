"""The step programs' scopes, the reduction of a device trace by scope
(``monitor/device_trace.py``), the program's spans on the profiler's
clock, and the set-up counters of ``monitor/jit_watch.py``."""

import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.monitor import health
from deeplearning4j_tpu.monitor.device_trace import (
    GROUPS, estimate_cost, find_trace, hlo_modules, instruction_costs,
    parse_hlo_line, parse_op_name, publish, reduce, table)
from deeplearning4j_tpu.nn.computation_graph import ComputationGraph
from deeplearning4j_tpu.nn.conf.neural_net_configuration import \
    NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL_TRACE = os.path.join(HERE, "benchmark", "data",
                           "small_trace.xplane.pb")
SCOPE_TRACE = os.path.join(HERE, "data", "scope_trace.xplane.pb.gz")
TOY_FIT_TRACE = os.path.join(HERE, "benchmark", "data",
                             "toy_fit_trace.xplane.pb.gz")


def _builder():
    return (NeuralNetConfiguration.builder().seed(1).updater("nesterovs")
            .learning_rate(0.1).weight_init("xavier").activation("tanh")
            .l2(1e-4))


def _mln():
    conf = (_builder().list()
            .layer(DenseLayer(n_in=4, n_out=6))
            .layer(OutputLayer(n_in=6, n_out=3, activation="softmax",
                               loss="mcxent")))
    return MultiLayerNetwork(conf.build()).init()


def _graph():
    conf = (_builder().graph_builder().add_inputs("in")
            .add_layer("d", DenseLayer(n_in=4, n_out=6), "in")
            .add_layer("o", OutputLayer(n_in=6, n_out=3,
                                        activation="softmax",
                                        loss="mcxent"), "d")
            .set_outputs("o"))
    return ComputationGraph(conf.build()).init()


CONTAINERS = {
    "mln": (_mln, ["0_DenseLayer", "1_OutputLayer"]),
    "graph": (_graph, ["d", "o"]),
}


def _op_names(net, program: str):
    """The ``op_name`` of every instruction in the compiled HLO text of
    one of ``net``'s programs."""
    graph = isinstance(net, ComputationGraph)
    x = jnp.ones((8, 4), jnp.float32)
    y = jnp.eye(3, dtype=jnp.float32)[jnp.arange(8) % 3]
    one = (lambda a: (a,)) if graph else (lambda a: a)
    state = (net.params, net.updater_state, net.net_state, 0)
    if program == "output":
        lowered = net._output_fn.lower(net.params, net.net_state, one(x),
                                       None)
    elif program == "train_step":
        lowered = net._train_step.lower(
            *state, one(x), one(y), None, None, net._rng_key)
    elif program == "multi_train_step":
        lowered = net._multi_train_step.lower(
            *state, one(x[None]), one(y[None]), None, None, net._rng_key)
    else:
        lowered = net._gather_train_step.lower(
            *state, one(x), one(y), net._rng_key, net._rng_key, 0, 2, 2, 4,
            True, 0, one(None), 0, 2)
    return set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))


@pytest.mark.parametrize(
    "program", ["train_step", "multi_train_step", "gather_train_step"])
@pytest.mark.parametrize("container", sorted(CONTAINERS))
def test_train_programs_carry_the_scopes(container, program):
    """The step is built once (``nn/network.py``): each of its three
    programs, from either container, carries every scope."""
    build, layers = CONTAINERS[container]
    parsed = {parse_op_name(n) for n in _op_names(build(), program)}
    for name in layers:
        assert (f"layer.{name}", "forward") in parsed
        assert (f"layer.{name}", "backward") in parsed
        assert (f"update.{name}", "other") in parsed
    assert ("loss", "forward") in parsed and ("loss", "backward") in parsed
    assert ("reg", "other") in parsed
    assert (("ingest.gather", "other") in parsed) == \
        (program == "gather_train_step")
    assert ("health", "other") in parsed
    assert {scope.split(".")[0] for scope, _ in parsed} <= \
        set(GROUPS) | {"unscoped"}


@pytest.mark.parametrize("container", sorted(CONTAINERS))
def test_output_program_carries_bare_layer_scopes(container):
    build, layers = CONTAINERS[container]
    names = _op_names(build(), "output")
    for name in layers:
        assert any(f"/layer.{name}/" in n for n in names)
    assert not any("jvp(" in n or "transpose(" in n for n in names)
    parsed = {parse_op_name(n) for n in names}
    assert {(f"layer.{n}", "forward") for n in layers} <= parsed
    assert not any(scope.startswith(("update.", "loss", "health"))
                   for scope, _ in parsed)


@pytest.mark.parametrize("container", sorted(CONTAINERS))
def test_scopes_change_no_number(container, monkeypatch):
    """Scopes are metadata: a step traced without them computes the same
    bits."""
    import contextlib
    build, _ = CONTAINERS[container]
    x = np.random.default_rng(0).random((8, 4), dtype=np.float32)
    y = np.eye(3, dtype=np.float32)[np.arange(8) % 3]
    scoped = build()
    scoped.fit(x, y)
    monkeypatch.setattr(monitor, "scope",
                        lambda *a, **k: contextlib.nullcontext())
    bare = build()
    bare.fit(x, y)
    assert scoped.score() == bare.score()
    np.testing.assert_array_equal(scoped.get_flat_params(),
                                  bare.get_flat_params())


@pytest.mark.parametrize("op_name,expected", [
    ("jit(multi)/while/body/closed_call/jvp(layer.res2a_branch2a)"
     "/dot_general", ("layer.res2a_branch2a", "forward")),
    ("jit(multi)/while/body/closed_call/transpose(jvp(layer.bn_conv1))"
     "/reduce_sum", ("layer.bn_conv1", "backward")),
    ("jit(multi)/while/body/update.res2a_branch2a/mul",
     ("update.res2a_branch2a", "other")),
    ("jit(multi)/while/body/ingest.gather/gather",
     ("ingest.gather", "other")),
    ("jit(multi)/while/body/health/sqrt", ("health", "other")),
    ("jit(multi)/while/body/reg/square", ("reg", "other")),
    ("jit(step)/jvp(loss)/jit(log_softmax)/exp", ("loss", "forward")),
    ("jit(step)/transpose(jvp(loss))/mul", ("loss", "backward")),
    ("jit(step)/transpose(jvp(precision.cast))/convert_element_type",
     ("precision.cast", "backward")),
    ("jit(run)/layer.1_OutputLayer/dot_general",
     ("layer.1_OutputLayer", "forward")),
    # XLA joins the names of merged instructions: the first wins
    ("jit(multi)/while/body/transpose(jvp(layer.0_Dense))/dot_general;"
     "jit(multi)/while/body/update.0_Dense/mul",
     ("layer.0_Dense", "backward")),
    ("jit(multi)/while/body/add;jit(multi)/while/body/update.0_Dense/mul",
     ("unscoped", "other")),
    # nothing of the grammar
    ("jit(chain)/dot_general", ("unscoped", "other")),
    ("", ("unscoped", "other")),
    ("params[0]['W']", ("unscoped", "other")),
    # a jitted function that happens to be called like a scope
    ("jit(loss)/mul", ("unscoped", "other")),
    ("jit(step)/jvp(jit(health))/mul", ("unscoped", "other")),
    # vmap and other transformations wrap without naming a pass
    ("jit(f)/vmap(layer.a)/mul", ("layer.a", "forward")),
    ("jit(f)/vmap(update.a)/mul", ("update.a", "other")),
])
def test_parse_op_name(op_name, expected):
    assert parse_op_name(op_name) == expected


def test_scope_refuses_a_group_outside_the_grammar_and_a_slash():
    with pytest.raises(ValueError):
        monitor.scope("layers", "a")

    @jax.jit
    def f(x):
        with monitor.scope("layer", "block/conv"):
            return x * 2.0
    text = f.lower(jnp.ones(3)).compile().as_text()
    assert "layer.block_conv" in text


# ------------------------------------------------- the recorded chip traces
@pytest.fixture(scope="module")
def small():
    return reduce(SMALL_TRACE, window="bench/window")


def test_small_trace_busy_seconds_equal_the_benchmarks(small):
    """Two reductions, one file: the program's and the benchmark's
    (``benchmark/xplane.py``) agree on the device-busy seconds."""
    from benchmark import xplane
    theirs = xplane.reduce_events(xplane.read_events(SMALL_TRACE))
    assert small["busy_s"] == pytest.approx(theirs["busy_s"], abs=1e-9)
    assert small["window_s"] == pytest.approx(theirs["window_s"], abs=1e-9)
    assert small["idle_share"] == pytest.approx(theirs["idle_share"],
                                                abs=1e-9)
    assert small["devices"] == 1


def test_small_trace_rows_sum_to_busy_and_nothing_is_dropped(small):
    assert sum(r[2] for r in small["by_scope"]) == \
        pytest.approx(small["busy_s"], abs=1e-12)
    assert sum(small["by_pass"].values()) == \
        pytest.approx(small["busy_s"], abs=1e-12)
    assert sum(small["by_group"].values()) == \
        pytest.approx(small["busy_s"], abs=1e-12)
    # a program without scopes: everything is unscoped, by opcode
    assert [r[:2] for r in small["by_scope"]] == [["unscoped", "other"]]
    assert small["by_group"]["unscoped"] == small["by_scope"][0][2]
    opcodes = {r[0]: r for r in small["unscoped_by_opcode"]}
    assert set(opcodes) == {"fusion", "copy-start", "copy-done"}
    # two of the three units lie inside ``bench/window`` on the device's
    # clock: 8 fusions and one copy pair each
    assert opcodes["fusion"][2] == 16
    assert opcodes["copy-start"][2] == opcodes["copy-done"][2] == 2
    assert sum(r[1] for r in small["unscoped_by_opcode"]) == \
        pytest.approx(small["busy_s"], abs=1e-12)


def test_small_trace_op_names_come_from_the_hlo_module():
    (name, instructions), = hlo_modules(SMALL_TRACE).items()
    assert re.fullmatch(r"jit_chain\(\d+\)", name)
    for i in range(1, 9):
        assert instructions[f"fusion.{i}"] == (
            "fusion", "jit(chain)/dot_general", (), False)
    # XLA gave the prefetch no name: it takes its consumer's
    assert instructions["copy-start"] == (
        "copy-start", "jit(chain)/dot_general", (), True)
    assert instructions["copy-done"] == (
        "copy-done", "jit(chain)/dot_general", (), True)


def test_small_trace_idle_time_by_the_benchmarks_spans(small):
    """The benchmark's ``bench/...`` annotations have the form of program
    spans, so the idle split works on them as on ``fit/...``."""
    idle = dict(small["idle_by_span"])
    assert idle["bench/sleep"] > 0.058
    assert sum(idle.values()) == pytest.approx(
        small["window_s"] - small["busy_s"], rel=1e-9)
    assert "bench/window" not in idle


def test_without_its_window_span_the_device_events_bound_the_session():
    whole = reduce(SMALL_TRACE)          # no ``profiler/capture`` in it
    assert whole["by_scope"][0][3] == 30     # all three units
    assert whole["window_s"] < 0.05
    assert reduce(os.path.dirname(SMALL_TRACE))["busy_s"] == \
        whole["busy_s"]


def test_nothing_to_read_gives_nothing(tmp_path):
    assert find_trace(str(tmp_path)) is None
    assert reduce(str(tmp_path)) is None


@pytest.fixture(scope="module")
def scoped():
    return reduce(SCOPE_TRACE)


def test_scope_trace_is_small_and_says_how_it_was_made():
    assert os.path.getsize(SCOPE_TRACE) < 100_000
    assert os.path.isfile(os.path.join(HERE, "data",
                                       "record_scope_trace.py"))


def test_scope_trace_splits_the_gather_step(scoped):
    """A two-layer net's gather step, 40 fused steps, recorded on the
    chip (``tests/data/record_scope_trace.py``).  Forward, backward and
    the gather have kernels of their own.  The updater has none: XLA
    fuses ``update.*`` into the weight-gradient kernels, whose time goes
    to the layer's backward pass, so it shows in ``fused_in_by_group``
    alone."""
    assert scoped["devices"] == 1 and scoped["busy_s"] > 0
    assert sum(r[2] for r in scoped["by_scope"]) == \
        pytest.approx(scoped["busy_s"], rel=1e-9)
    assert scoped["by_pass"]["forward"] > 0
    assert scoped["by_pass"]["backward"] > scoped["by_pass"]["forward"]
    assert scoped["by_group"]["ingest"] > 0
    assert scoped["by_group"]["layer"] > 0.4 * scoped["busy_s"]
    assert scoped["by_group"]["health"] > 0
    assert scoped["by_group"]["update"] == 0
    assert scoped["fused_in_by_group"]["update"] > 0
    assert scoped["fused_in_by_group"]["update"] <= \
        scoped["by_pass"]["backward"] * (1 + 1e-9)
    assert scoped["by_group"]["unscoped"] < 0.10 * scoped["busy_s"]
    assert 0 <= scoped["inherited_s"] < scoped["busy_s"]
    rows = {(r[0], r[1]): r for r in scoped["by_scope"]}
    assert rows[("ingest.gather", "other")][3] >= 40      # events
    for layer in ("0_DenseLayer", "1_OutputLayer"):
        assert rows[(f"layer.{layer}", "forward")][2] > 0
        assert rows[(f"layer.{layer}", "backward")][2] > 0


def test_scope_trace_fusions_say_what_they_hold():
    modules = hlo_modules(SCOPE_TRACE)
    (step,) = [m for name, m in modules.items()
               if name.startswith("jit_multi(")]
    holding = [ins for ins in step.values()
               if ins[0] == "fusion" and "update" in ins[2]]
    owners = {parse_op_name(op_name) for _, op_name, _, _ in holding}
    assert any(scope.startswith("layer.") and pass_ == "backward"
               for scope, pass_ in owners)
    assert not any(scope.startswith("update.") for scope, _ in owners)
    # nameless copies of a prefetch take their consumer's name
    assert any(ins[3] for ins in step.values())


def test_scope_trace_idle_time_goes_to_the_programs_spans(scoped):
    idle = dict(scoped["idle_by_span"])
    assert set(idle) <= {"fit/epoch", "fit/stage", "fit/dispatch",
                         "fit/score_wait", "no_span"}
    assert "fit/dispatch" in idle or "fit/score_wait" in idle
    assert sum(idle.values()) == pytest.approx(
        scoped["window_s"] - scoped["busy_s"], rel=1e-6)


def test_table_prints_every_section(scoped):
    text = table(scoped, top=5)
    for heading in ("by pass:", "by group (own", "(scope, pass) rows",
                    "idle time of the first device"):
        assert heading in text
    assert "ingest.gather" in text


# ------------------------- the compiler's costs and the idle time by cause
TRACES = {"small": (SMALL_TRACE, "bench/window"),
          "scope": (SCOPE_TRACE, "profiler/capture"),
          "toy_fit": (TOY_FIT_TRACE, "bench/window")}


@pytest.fixture(scope="module")
def reports():
    return {name: reduce(path, window=window)
            for name, (path, window) in TRACES.items()}


def test_small_trace_costs_are_the_compilers_own_numbers():
    """The stats of each instruction's ``XEventMetadata``: a product
    whose operand the layout put on the chip (``S(1)``) moves no HBM
    byte; the prefetch that brought the operand there reads it from HBM
    (space 1), once."""
    (program, lines), = instruction_costs(SMALL_TRACE).items()
    assert program > 2 ** 32
    by_name = {line.split(" = ")[0]: cost for line, cost in lines.items()}
    assert by_name["%fusion.8"] == (
        2151677952.0, 0.0, 0.0, 6291456.0, "convolution fusion", "counted")
    assert by_name["%copy-start"] == (
        0.0, 2097152.0, 0.0, 0.0, "copy-start", "counted")
    # its done writes the chip's memory, and the last product two bytes
    assert by_name["%copy-done"][:4] == (0.0, 0.0, 0.0, 2097152.0)
    assert by_name["%fusion.1"][1:3] == (0.0, 2.0)


def test_small_trace_counts_each_byte_once(small):
    """Two units in the window: two prefetches of 2 MiB and two scalar
    results.  ``copy-start`` stands on ``XLA Ops`` and on ``Async XLA
    Ops``, and the second line adds nothing."""
    assert small["hbm_bytes"] == 2 * 2097152 + 2 * 2
    assert small["flops"] == 2 * (7 * 2151677952 + 2152726528)
    assert small["estimated_s"] == small["uncounted_s"] == 0.0
    assert all(len(row) == 4 for row in small["by_scope"])


@pytest.mark.parametrize("name", sorted(TRACES))
def test_cost_by_scope_has_by_scopes_rows_in_its_order(reports, name):
    report = reports[name]
    assert [r[:3] for r in report["cost_by_scope"]] == \
        [r[:3] for r in report["by_scope"]]
    assert all(len(r) == 7 for r in report["cost_by_scope"])
    assert all(len(r) == 4 for r in report["by_scope"])
    assert sum(r[3] + r[4] for r in report["cost_by_scope"]) == \
        pytest.approx(report["hbm_bytes"], rel=1e-12)
    assert sum(r[5] for r in report["cost_by_scope"]) == \
        pytest.approx(report["flops"], rel=1e-12)
    assert sum(r[6] for r in report["cost_by_scope"]) == \
        pytest.approx(report["estimated_s"], abs=1e-15)
    assert report["hbm_bytes"] > 0 and report["flops"] > 0
    assert report["uncounted_s"] == 0.0


@pytest.mark.parametrize("name", sorted(TRACES))
def test_idle_time_by_cause_sums_to_the_idle_time(reports, name):
    report = reports[name]
    idle = report["window_s"] - report["busy_s"]
    assert report["idle_in_program_s"] + report["idle_between_programs_s"] \
        == pytest.approx(idle, abs=1e-9)
    assert report["idle_in_program_s"] > 0
    assert report["idle_between_programs_s"] > 0
    assert sum(r[2] for r in report["gaps_in_program"]) == \
        pytest.approx(report["idle_in_program_s"], abs=1e-9)
    assert sum(r[1] for r in report["idle_between_by_span"]) == \
        pytest.approx(report["idle_between_programs_s"], abs=1e-9)
    seconds = [r[2] for r in report["gaps_in_program"]]
    assert seconds == sorted(seconds, reverse=True)
    # what the host's spans cover of the time BETWEEN programs is part
    # of what they cover of all idle time
    every = dict(report["idle_by_span"])
    for span, sec in report["idle_between_by_span"]:
        assert sec <= every[span] + 1e-12


def test_scope_trace_gaps_name_who_waited_and_what_came_before(scoped):
    rows = {(r[0], r[1]): r for r in scoped["gaps_in_program"]}
    # the health statistics wait behind the kernels before them, 40 steps
    assert rows[("health", "fusion")][3] >= 40
    # a custom call is named by its target, and where it joins the
    # slices of a fetch the consumer of the fetch is who waited
    assert ("unscoped", "ConcatBitcast") in rows
    assert ("unscoped", "slice-done") in rows
    assert not any(before in ("custom-call", "async-done")
                   for _, before in rows)
    # a program's first operation, and the time after its last one
    assert any(before == "program-start" for _, before in rows)
    assert any(scope == "program-end" for scope, _ in rows)
    # the host waited for the scores while no program ran
    between = dict(scoped["idle_between_by_span"])
    assert between["fit/score_wait"] > 0.5 * sum(between.values())
    assert scoped["idle_between_programs_s"] > \
        10 * scoped["idle_in_program_s"]


def test_a_custom_call_the_compiler_did_not_count_is_estimated(scoped):
    """``custom-call.10`` (a ``ConcatBitcast`` of four slices) has
    operands and all-zero stats: its seconds are ``estimated_s``, its
    bytes those of its operands and result outside ``S(n)``, here none
    (all five are on the chip).  ``custom-call.5`` (``AllocateBuffer``)
    has nothing in and counts as nothing."""
    step = max(instruction_costs(SCOPE_TRACE).values(), key=len)
    by_name = {line.split(" = ")[0]: cost for line, cost in step.items()}
    assert by_name["%custom-call.10"] == (
        0.0, 0.0, 0.0, 0.0, "custom-call", "estimated")
    assert by_name["%custom-call.5"][5] == "counted"
    assert 0 < scoped["estimated_s"] < 1e-6
    assert scoped["uncounted_s"] == 0.0


KERNEL = ("%custom-call.7 = (bf16[64,128]{1,0:T(8,128)(2,1)}, "
          "f32[64]{0:T(128)S(1)}) custom-call(bf16[64,512]{1,0:T(8,128)(2,1)}"
          " %q, bf16[4,1024,512]{2,1,0:T(8,128)(2,1)} %ring, "
          "s32[4]{0:T(128)S(1)} %cursor), "
          "custom_call_target=\"tpu_custom_call\"")


@pytest.mark.parametrize("line, expected", [
    (KERNEL, (0.0, 64 * 512 * 2 + 4 * 1024 * 512 * 2.0, 64 * 128 * 2.0,
              0.0, "custom-call", "estimated")),
    # a result that is an operand again is that operand, once
    (KERNEL + ", output_to_operand_aliasing={{0}: (0, {})}",
     (0.0, 64 * 512 * 2 + 4 * 1024 * 512 * 2.0, 0.0, 0.0, "custom-call",
      "estimated")),
    # the done of a pair adds nothing: its start holds the fetch
    ("%slice-done.8 = bf16[256,2048]{1,0:T(8,128)(2,1)} async-done("
     "((bf16[1024,2048]{1,0}), bf16[256,2048]{1,0}, s32[]{:S(2)}) "
     "%slice-start.8)", (0.0, 0.0, 0.0, 0.0, "async-done", "estimated")),
    ("no HLO line at all", (0.0, 0.0, 0.0, 0.0, "", "uncounted")),
    ("%cut = f32[8]{0} fusion(f32[8]{0} %p", (0.0, 0.0, 0.0, 0.0, "",
                                             "uncounted")),
], ids=["kernel", "aliased", "done", "no_line", "cut_short"])
def test_estimate_cost_counts_what_the_layout_leaves_in_hbm(line, expected):
    assert estimate_cost(line) == expected


def test_parse_hlo_line_splits_result_opcode_operands_and_attributes():
    result, opcode, operands, attributes = parse_hlo_line(KERNEL)
    assert result.startswith("(bf16[64,128]") and result.endswith("S(1)})")
    assert opcode == "custom-call"
    assert operands.startswith("bf16[64,512]") and \
        operands.endswith("%cursor")
    assert attributes == 'custom_call_target="tpu_custom_call"'
    assert parse_hlo_line("%c = f32[]{:T(128)} constant(0)")[1:3] == \
        ("constant", "0")


# ...................................................... a hand-made plane
def _varint(value):
    out = bytearray()
    while True:
        out.append((value & 0x7F) | (0x80 if value > 0x7F else 0))
        value >>= 7
        if not value:
            return bytes(out)


def _message(*fields):
    """``(number, value)`` pairs as one serialized message: ints as
    varints, bytes and strings length-delimited."""
    out = bytearray()
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return bytes(out)


LINE = "%fusion.1 = f32[8]{0:T(128)} fusion(f32[8]{0:T(128)} %p), kind=kLoop"
LOOP = ("%while.1 = (s32[]{:T(128)}, f32[8]{0:T(128)}) while((s32[]{:T(128)}"
        ", f32[8]{0:T(128)}) %tuple.1), condition=%cond.1, body=%body.1")
UNREADABLE = "an event the profiler named by hand"
#: metadata id -> (name, program id, flops, HBM bytes read, category);
#: the same HLO line in two programs, with costs of their own, and a
#: loop that carries its body's numbers
EVENTS = {1: (LINE, 111, 1000, 64, "loop fusion"),
          2: (LINE, 222, 5000, 4096, "loop fusion"),
          3: (UNREADABLE, None, 0, 0, ""), 4: ("jit_a(111)", None, 0, 0, ""),
          5: ("jit_b(222)", None, 0, 0, ""),
          6: (LOOP, 222, 777777, 8192, "while")}
STATS = {1: "program_id", 2: "flops", 3: "bytes_accessed",
         4: "memory_access_breakdown", 5: "hlo_category"}


def _hand_made_plane():
    """One device, two programs, times in microseconds:

    ``jit_a`` runs 10-40: ``fusion.1`` 10-20, a gap, ``fusion.1`` 25-35,
    then 5 idle to the program's end; nothing runs 40-60; ``jit_b`` runs
    60-95: 2 idle, ``fusion.1`` 62-80, an event without stats 80-90, a
    loop of no round 90-92 (a leaf that carries its body's stats)."""
    def metadata(ident):
        name, program, flops, read, category = EVENTS[ident]
        stats = [] if program is None else [
            _message((1, 1), (3, program)), _message((1, 2), (4, flops)),
            _message((1, 3), (4, read)),
            _message((1, 4), (6, _message((1, _message(
                (1, 1), (2, 1), (3, read)))))),
            _message((1, 5), (5, category))]
        return _message((1, ident), (2, _message(
            (1, ident), (2, name), *((5, s) for s in stats))))

    def line(name, events):
        return _message((2, name), (3, 0), *(
            (4, _message((1, ident), (2, start * 10 ** 6),
                         (3, (end - start) * 10 ** 6)))
            for ident, start, end in events))

    plane = _message(
        (2, "/device:TPU:0"),
        (3, line("XLA Modules", [(4, 10, 40), (5, 60, 95)])),
        (3, line("XLA Ops", [(1, 10, 20), (1, 25, 35), (2, 62, 80),
                             (3, 80, 90), (6, 90, 92)])),
        *((4, metadata(i)) for i in EVENTS),
        *((5, _message((1, i), (2, _message((1, i), (2, name)))))
          for i, name in STATS.items()))
    return _message((1, plane))


@pytest.fixture(scope="module")
def hand_made(tmp_path_factory):
    path = tmp_path_factory.mktemp("plane") / "hand.xplane.pb"
    path.write_bytes(_hand_made_plane())
    return reduce(str(path))


def test_a_gap_inside_a_program_and_a_gap_outside_split_as_said(hand_made):
    us = 1e-6
    assert hand_made["window_s"] == pytest.approx(82 * us)
    assert hand_made["busy_s"] == pytest.approx(50 * us)
    assert hand_made["idle_in_program_s"] == pytest.approx(12 * us)
    assert hand_made["idle_between_programs_s"] == pytest.approx(20 * us)
    assert hand_made["idle_between_by_span"] == [
        ["no_span", pytest.approx(20 * us)]]
    rows = {(r[0], r[1]): (r[2], r[3]) for r in hand_made["gaps_in_program"]}
    assert rows == {
        ("unscoped", "fusion"): (pytest.approx(5 * us), 1),
        ("program-end", "fusion"): (pytest.approx(5 * us), 1),
        ("unscoped", "program-start"): (pytest.approx(2 * us), 1)}
    assert [r[:2] for r in hand_made["gaps_in_program"]][-1] == \
        ["unscoped", "program-start"]


def test_two_programs_with_one_hlo_line_keep_their_own_costs(hand_made):
    """``cg.token_step`` and ``cg.fork_state`` can hold the same line:
    an event is matched by the program that contains it."""
    assert hand_made["flops"] == 2 * 1000 + 5000
    assert hand_made["hbm_bytes"] == 2 * 64 + 4096
    (row,) = hand_made["cost_by_scope"]
    assert row[:2] == ["unscoped", "other"] and row[3:6] == [
        2 * 64 + 4096, 0.0, 2 * 1000 + 5000]


def test_an_event_nothing_can_be_read_of_is_uncounted_not_dropped(hand_made):
    assert hand_made["uncounted_s"] == pytest.approx(10e-6)
    assert hand_made["estimated_s"] == 0.0
    assert hand_made["by_scope"] == [
        ["unscoped", "other", pytest.approx(50e-6), 5]]


def test_a_loop_that_ran_no_round_counts_none_of_its_bodys_numbers(
        hand_made, tmp_path):
    """A ``while`` carries the stats of its body.  Where the body ran,
    its events are the leaves and the loop is a container; where it ran
    no round the loop itself is a leaf, and did nothing of what the
    stats say (``ax_k1``'s loop over a share's further rounds)."""
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_hand_made_plane())
    assert instruction_costs(str(path))[222][LOOP] == (
        0.0, 0.0, 0.0, 0.0, "while", "counted")
    # the sums of the test above hold with the loop's 2 us in the window
    assert hand_made["flops"] == 2 * 1000 + 5000
    assert ["while", pytest.approx(2e-6), 1] in \
        hand_made["unscoped_by_opcode"]


GATHER = ("%custom-call.9 = bf16[8,4,8,128]{3,2,1,0:T(8,128)(2,1)} "
          "custom-call(s32[8,2048]{1,0:T(8,128)S(1)} %slots, "
          "bf16[8,32768,8,128]{3,2,1,0:T(8,128)(2,1)} %ring), "
          "custom_call_target=\"tpu_custom_call\"")


@pytest.mark.parametrize("declared, stats_there, expected", [
    # the kernel declared 33.6 MB of a 537 MB ring: the declared count,
    # less the 64 KB the breakdown says it writes, is what it read
    (33_751_040, True, (1e6, 33_751_040.0 - 65_536, 65_536.0, 0.0,
                        "custom-call", "counted")),
    # a declared count no smaller than the operands changes nothing
    (2 * 536_936_448, True, (1e6, 536_936_448.0, 65_536.0, 0.0,
                             "custom-call", "counted")),
    # nothing declared, all-zero stats: the operands whole, as today
    (0, False, (0.0, 8 * 32768 * 8 * 128 * 2.0, 8 * 4 * 8 * 128 * 2.0, 0.0,
                "custom-call", "estimated")),
], ids=["declared", "declared_larger", "undeclared"])
def test_a_kernel_that_declares_its_bytes_is_taken_at_its_word(
        tmp_path, declared, stats_there, expected):
    """``pl.CostEstimate`` reaches the trace as ``bytes_accessed`` beside
    a breakdown that holds every operand whole (read from the traced
    gathered sparse attention): a ``tpu_custom_call`` that fetches part
    of an operand it left in HBM counts what it declared."""
    breakdown = _message(
        (1, _message((1, 1), (2, 1), (3, 536_936_448))),
        (1, _message((1, 2), (2, 1), (3, 65_536)))) if stats_there else b""
    stats = [_message((1, 1), (3, 7)),
             _message((1, 2), (4, 10 ** 6 if stats_there else 0)),
             _message((1, 3), (4, declared)),
             _message((1, 4), (6, breakdown)),
             _message((1, 5), (5, "custom-call"))]
    plane = _message(
        (2, "/device:TPU:0"),
        (4, _message((1, 1), (2, _message(
            (1, 1), (2, GATHER), *((5, s) for s in stats))))),
        *((5, _message((1, i), (2, _message((1, i), (2, name)))))
          for i, name in STATS.items()))
    path = tmp_path / "kernel.xplane.pb"
    path.write_bytes(_message((1, plane)))
    assert instruction_costs(str(path))[7][GATHER] == expected


def test_table_prints_the_costs_and_the_idle_time_by_cause(scoped):
    text = table(scoped, top=5)
    for heading in ("HBM traffic", "GB/s", "TFLOP/s", "estimated from",
                    "uncounted", "idle while a program ran",
                    "between programs", "opcode before"):
        assert heading in text
    assert "program-start" in table(scoped, top=50)


def test_publish_keeps_the_bytes_and_the_idle_time_by_cause(scoped):
    publish(scoped)
    snapshot = monitor.snapshot()
    traffic = snapshot["device_scope_hbm_bytes"]["values"]
    assert len(traffic) == len(scoped["cost_by_scope"])
    assert sum(traffic.values()) == pytest.approx(scoped["hbm_bytes"])
    assert snapshot["device_idle_in_program_seconds"]["values"][""] == \
        pytest.approx(scoped["idle_in_program_s"])
    assert snapshot["device_idle_between_programs_seconds"]["values"][""] \
        == pytest.approx(scoped["idle_between_programs_s"])


# --------------------------------------------- spans on the profiler's clock
def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return [(ev.name, ev.start_ns, ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_a_span_is_an_event_of_the_profilers_host_plane(tmp_path):
    monitor.reset()
    with monitor.span("fit/outside"):
        pass
    with monitor.device_trace(str(tmp_path)) as trace:
        with monitor.span("fit/dispatch", steps=3, fused=1):
            time.sleep(0.002)
    assert trace.report is None           # the CPU has no TPU plane
    assert trace.open is False
    events = {name: (start, dur) for name, start, dur
              in _host_events(str(tmp_path))}
    assert "fit/outside" not in events
    start, dur = events["fit/dispatch"]
    assert dur >= 2e6
    session_start, session_dur = events["profiler/capture"]
    assert session_start <= start
    assert start + dur <= session_start + session_dur
    # the ring still has both, and the capture's window
    ring = [e["name"] for e in monitor.tracer().events()]
    assert ring == ["fit/outside", "fit/dispatch", "profiler/capture"]
    (capture,) = monitor.tracer().events(name="profiler/capture")
    assert capture["attrs"]["log_dir"] == str(tmp_path)


def test_a_span_costs_nothing_observable_without_a_session():
    def spans(n):
        t0 = time.perf_counter()
        for _ in range(n):
            with monitor.span("fit/dispatch", steps=1, fused=1):
                pass
        return (time.perf_counter() - t0) / n
    spans(200)
    assert min(spans(2000) for _ in range(3)) < 100e-6


def test_fit_opens_the_spans_of_the_epoch_cache_path():
    monitor.reset()
    net = _mln()
    x = np.random.default_rng(0).random((16, 4), dtype=np.float32)
    y = np.eye(3, dtype=np.float32)[np.arange(16) % 3]
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    net.fit(ListDataSetIterator(DataSet(x, y), 4), epochs=2)
    assert np.isfinite(net.score())
    assert net.score() == net.score()     # a host value is not waited for
    events = monitor.tracer().events()
    by_name = {}
    for e in events:
        by_name.setdefault(e["name"], []).append(e)
    (epoch,) = by_name["fit/epoch"]
    (stage,) = by_name["fit/stage"]
    (dispatch,) = by_name["fit/dispatch"]
    assert stage["parent"] == dispatch["parent"] == epoch["id"]
    assert dispatch["attrs"] == {"steps": 8, "fused": 2}
    assert len(by_name["fit/score_wait"]) == 1


# ------------------------------------------------------------ set-up counters
SETUP_COUNTERS = ("jit_trace_seconds_total", "jit_lower_seconds_total",
                  "jit_backend_seconds_total")


def _seconds(name, fn):
    return monitor.registry().counter(name).value(fn=fn)


def test_setup_counters_grow_on_a_first_call_and_not_on_a_second():
    monitor.reset()

    def f(x):
        for _ in range(4):
            x = jnp.tanh(x @ x) + jnp.take(x, jnp.arange(8), axis=0)
        return x.sum()

    watched = monitor.watched_jit(f, name="test.setup_counters")
    x = jnp.ones((8, 8), jnp.float32)
    t0 = time.perf_counter()
    watched(x).block_until_ready()
    wall = time.perf_counter() - t0
    first = {n: _seconds(n, "test.setup_counters")
             + _seconds(n, "test.setup_counters/cost_analysis")
             for n in SETUP_COUNTERS}
    assert all(v > 0 for v in first.values()), first
    # nested trace events are not counted twice: the stages add up to
    # less than the wall they ran in
    assert sum(first.values()) <= wall
    # the extra lowering behind the cost gauges is charged by name
    assert _seconds("jit_trace_seconds_total",
                    "test.setup_counters/cost_analysis") > 0
    snapshot = monitor.snapshot()
    watched(x).block_until_ready()
    assert monitor.snapshot()["jit_trace_seconds_total"] == \
        snapshot["jit_trace_seconds_total"]
    for n in SETUP_COUNTERS:
        assert _seconds(n, "test.setup_counters") + _seconds(
            n, "test.setup_counters/cost_analysis") == first[n]


def test_compiles_outside_a_watched_jit_are_unwatched():
    monitor.reset()
    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(5)).block_until_ready()
    assert _seconds("jit_backend_seconds_total", "unwatched") > 0
    assert _seconds("jit_trace_seconds_total", "unwatched") > 0


class _Clock:
    """A clock the test moves: the listener reads ``perf_counter``."""

    def __init__(self):
        self.now = 1000.0

    def perf_counter(self):
        return self.now


def test_nested_compile_events_add_up_to_wall_time(monkeypatch):
    """The listener sees ``(event, duration)`` at an event's end: an
    outer event adds only what its inner events have not counted."""
    from deeplearning4j_tpu.monitor import jit_watch
    monitor.reset()
    clock = _Clock()
    monkeypatch.setattr(jit_watch, "time", clock)
    jit_watch._compiling.__dict__.clear()   # this thread's earlier events
    event = "/jax/core/compile/jaxpr_trace_duration"
    with jit_watch._compiling_as("test.nested"):
        clock.now += 3.0                    # outer runs 1 s, then an inner
        jit_watch._on_compile_duration(event, 2.0)
        clock.now += 1.5                    # 0.5 s of outer, 1 s of inner
        jit_watch._on_compile_duration(event, 1.0)
        clock.now += 0.25
        jit_watch._on_compile_duration(event, 4.75)     # the outer one
    assert _seconds("jit_trace_seconds_total", "test.nested") == \
        pytest.approx(4.75)
    # a sibling after it, on its own, and an event of another kind
    clock.now += 2.0
    jit_watch._on_compile_duration(event, 0.5)
    jit_watch._on_compile_duration("/jax/some/other_event", 5.0)
    assert _seconds("jit_trace_seconds_total", "unwatched") == \
        pytest.approx(0.5)
    assert _seconds("jit_lower_seconds_total", "unwatched") == 0
    jit_watch._compiling.__dict__.clear()


def test_thousands_of_sibling_events_still_add_up(monkeypatch):
    """ResNet-50's step fires 7,500 trace events inside one: the parent
    must find what all of them counted, not only the latest few, also
    when older top-level events lie before it."""
    from deeplearning4j_tpu.monitor import jit_watch
    monitor.reset()
    clock = _Clock()
    monkeypatch.setattr(jit_watch, "time", clock)
    jit_watch._compiling.__dict__.clear()   # this thread's earlier events
    event = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    clock.now += 1.0
    jit_watch._on_compile_duration(event, 1.0)          # an older one
    with jit_watch._compiling_as("test.siblings"):
        clock.now += 0.5
        siblings = 3 * jit_watch._PRUNE_EVERY
        for _ in range(siblings):
            clock.now += 0.002
            jit_watch._on_compile_duration(event, 0.001)
        clock.now += 0.5
        jit_watch._on_compile_duration(event, 1.0 + 0.002 * siblings)
    assert _seconds("jit_lower_seconds_total", "test.siblings") == \
        pytest.approx(1.0 + 0.002 * siblings)
    assert _seconds("jit_lower_seconds_total", "unwatched") == \
        pytest.approx(1.0)
    # an hour on, what no open event can enclose is forgotten
    clock.now += 2 * jit_watch._HORIZON_S
    for _ in range(jit_watch._PRUNE_EVERY):
        clock.now += 0.002
        jit_watch._on_compile_duration(event, 0.001)
    counted = jit_watch._compiling.counted[event]
    assert len(counted) <= jit_watch._PRUNE_EVERY
    jit_watch._compiling.__dict__.clear()   # the fake clock's leave too


def test_health_scope_is_written_once_in_the_monitor():
    """``layer_stats`` and ``guard_select`` carry the ``health`` scope
    themselves, so every step builder that calls them has it."""
    health.enable(policy="skip_update")
    try:
        def f(p, g):
            new = jax.tree.map(lambda a, b: a - b, p, g)
            vec, bad = health.layer_stats([p], [new], [g], 1.0)
            return health.guard_select(bad, new, p), vec
        text = jax.jit(f).lower({"W": jnp.ones(3)},
                                {"W": jnp.ones(3)}).compile().as_text()
    finally:
        health.reset()
    parsed = {parse_op_name(n)
              for n in re.findall(r'op_name="([^"]*)"', text)}
    assert ("health", "other") in parsed
