"""The reduction of a trace to the program's names (``bench/scopes.py``): on
a small trace recorded on one TPU v5e chip by ``record_scopes.py`` (two
step programs with one module name, each a scan over two named scopes,
the scopes swapped between them), and on spans and ops made by hand."""
import gzip
import json
from pathlib import Path

import pytest

from bench import scopes, trace

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded():
    prof = scopes.read(str(DATA / "small_scopes_trace.xplane.pb"))
    with gzip.open(DATA / "small_scopes_hlo.json.gz", "rt") as f:
        texts = json.load(f)
    return prof, texts


def test_scope_of_unwraps_transformations():
    assert scopes.scope_of("jit(f)/while/body/closed_call/attention/dot") \
        == "attention"
    assert scopes.scope_of("jit(f)/transpose(jvp(head_loss))/while/add") \
        == "head_loss"
    assert scopes.scope_of("jit(f)/transpose(jvp())/checkpoint/"
                           "rematted_computation/mlp/jit(silu)/add") == "mlp"
    assert scopes.scope_of("jit(f)/jvp(jit(_take))/select_n") is None
    assert scopes.scope_of("jit(f)/mlp/x/optimizer/mul") == "optimizer"


def test_instructions_are_keyed_as_the_trace_names_them():
    text = ("HloModule m\n\nENTRY %main {\n"
            '  %a = f32[2]{0} parameter(0), metadata={op_name="x"}\n'
            '  ROOT %b = (f32[2]{0:T(256)}, s32[]) fusion(%a, %a), '
            'kind=kLoop, metadata={op_name="jit(f)/jvp(mlp)/add"}\n}\n')
    assert scopes.instructions(text) == {
        ("%a", "f32[2]{0}", "parameter"): None,
        ("%b", "(f32[2]{0:T(256)}, s32[])", "fusion"): "mlp"}
    mod = scopes.Module(text)
    # the trace prints the operands' types and no metadata
    assert mod.find("%b = (f32[2]{0:T(256)}, s32[]) fusion(f32[2]{0} %a, "
                    "f32[2]{0} %a), kind=kLoop") == (True, "mlp")
    assert mod.find("%b = (f32[4]{0:T(256)}, s32[]) fusion(f32[4]{0} %a, "
                    "f32[4]{0} %a), kind=kLoop") == (False, None)


def test_exclusive_time_of_nested_ops():
    ops = [(0, 100, "%while"), (10, 30, "%a"), (40, 90, "%inner_while"),
           (45, 60, "%b"), (60, 85, "%c"), (120, 130, "%d")]
    got = {t: (own, leaf) for _, _, t, own, leaf in scopes.exclusive(ops)}
    assert got == {"%while": (30, False), "%a": (20, True),
                   "%inner_while": (10, False), "%b": (15, True),
                   "%c": (25, True), "%d": (10, True)}
    assert sum(v[0] for v in got.values()) == trace.union(ops, 0, 200)


def _span(name, start, end, **args):
    return scopes.Span(name, start, end, args, "python")


def test_step_host_time_and_the_move_from_spans_made_by_hand():
    spans = [_span("edl.step", 0, 100e6, step=0),
             _span("edl.step.wait", 10e6, 70e6, step=0),
             _span("edl.adjust.staged_reshard", 5e6, 20e6, adj=3, bytes=8),
             _span("edl.step", 100e6, 150e6, step=1),
             _span("edl.step.wait", 105e6, 140e6, step=1),
             _span("edl.adjust.stop_window", 140e6, 148e6, adj=3),
             _span("edl.adjust.ready", 141e6, 147e6, adj=3)]
    # step 0: 100 less the union of [5, 20) and [10, 70) = 35 ms;
    # step 1: 50 less [105, 148) = 7 ms
    assert scopes.step_host_ms(spans, 0, 200e6) == pytest.approx(21.0)
    # from the end of the last wait before ready, 140, to 147 ms
    assert scopes.adjust_move_ms(spans, 0, 200e6) == pytest.approx(7.0)
    assert scopes.adjust_move_ms(spans, 10e6, 200e6) is None
    assert scopes.step_host_ms(spans, 200e6, 300e6) is None


def test_each_module_run_is_matched_to_its_own_text(recorded):
    prof, texts = recorded
    runs = prof.modules[0]
    names = []
    for _, _, name in runs:
        if name not in names:
            names.append(name)
    assert len(names) == 2 and all(n.startswith("jit_step(") for n in names)
    matched = scopes.match_modules(prof, texts)
    for name, text in zip(names, texts):
        assert matched[name].table == scopes.instructions(text)


def test_each_leaf_op_gets_its_scope_from_its_own_module(recorded):
    prof, texts = recorded
    first, second = (scopes.Module(t) for t in texts)
    names = [n for _, _, n in prof.modules[0]]
    for s, e, name in prof.modules[0]:
        own = first if name == names[0] else second
        other = second if own is first else first
        ops = [o for o in prof.ops[0] if s <= o[0] < e]
        leaves = [o for o in scopes.exclusive(ops) if o[4]]
        got = scopes.scope_times(prof, texts, s, e)
        want = dict.fromkeys(scopes.SCOPES, 0.0)
        swapped = 0
        for _, _, text, own_ns, _ in leaves:
            scope = own.find(text)[1]
            if scope:
                want[scope] += own_ns / 1e9
            name = scopes.op_key(text)[0]
            if scope and any(k[0] == name and v and v != scope
                             for k, v in other.table.items()):
                swapped += 1
        assert swapped > 0      # the same instruction, the other scope
        for k in scopes.SCOPES:
            assert got[k] == pytest.approx(want[k])
        assert got["mlp"] > 0 and got["attention"] > 0


def test_while_containers_are_not_counted_in_a_scope(recorded):
    prof, texts = recorded
    ops = prof.ops[0]
    containers = [o for o in scopes.exclusive(ops) if not o[4]]
    assert containers and all("while" in o[2].split(" = ")[0]
                              for o in containers)
    lo, hi = ops[0][0], max(o[1] for o in ops)
    got = scopes.scope_times(prof, texts, lo, hi)
    inclusive = sum(o[1] - o[0] for o in containers) / 1e9
    assert got["containers"] < inclusive
    assert got["mlp"] + got["attention"] < got["busy"]


@pytest.mark.parametrize("config", [
    {}, {"scopes": ["dot_general"]}, {"scopes": ["div", "mlp", "div"]}])
@pytest.mark.parametrize("run", ["all", "first", "second"])
def test_scopes_and_the_remainder_add_up_to_the_busy_time(recorded, config,
                                                          run):
    prof, texts = recorded
    ops = prof.ops[0]
    lo, hi = ops[0][0], max(o[1] for o in ops)
    if run != "all":                    # one module run of the six
        lo, hi = prof.modules[0][0 if run == "first" else 1][:2]
    extra = scopes.extra_scopes(config)
    got = scopes.scope_times(prof, texts, lo, hi, extra)
    assert set(got) == set(scopes.SCOPES + extra) | {
        "unscoped", "containers", "busy"}
    parts = sum(got[k] for k in scopes.SCOPES + ("unscoped", "containers"))
    assert got["busy"] == pytest.approx(trace.union(ops, lo, hi) / 1e9)
    assert parts == pytest.approx(got["busy"], rel=0.01)
    for name in extra:
        assert 0 < got[name] < got["busy"]


def test_a_scope_named_in_a_configuration_is_read(recorded):
    """The matmul's ``dot_general`` lies inside ``mlp`` in the first
    program and inside ``attention`` in the second: named by a
    configuration, it is read from their ops, and no other reading
    moves."""
    prof, texts = recorded
    ops = prof.ops[0]
    lo, hi = ops[0][0], max(o[1] for o in ops)
    extra = scopes.extra_scopes({"scopes": ["mlp", "dot_general"]})
    assert extra == ("dot_general",)
    base = scopes.scope_times(prof, texts, lo, hi)
    got = scopes.scope_times(prof, texts, lo, hi, extra)
    assert 0.5 * (base["mlp"] + base["attention"]) < got["dot_general"] \
        <= base["mlp"] + base["attention"]
    assert {k: got[k] for k in base} == base


def test_an_added_scope_moves_no_other_reading(recorded):
    """``div`` and ``dot_general`` both lie in ``mlp``; naming one beside
    the other changes neither's reading."""
    prof, texts = recorded
    ops = prof.ops[0]
    lo, hi = ops[0][0], max(o[1] for o in ops)
    one = scopes.scope_times(prof, texts, lo, hi, ("dot_general",))
    two = scopes.scope_times(prof, texts, lo, hi, ("div", "dot_general"))
    assert two["div"] > 0
    assert {k: two[k] for k in one} == one


@pytest.mark.parametrize("extra", [["a/b"], "mlp", [3], ["1x"]])
def test_a_scope_that_is_no_name_is_refused(extra):
    with pytest.raises(ValueError, match="no names"):
        scopes.extra_scopes({"name": "c", "scopes": extra})
