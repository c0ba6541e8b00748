"""The harness takes a cell after the fifth with no edit to an accepted
file (ISSUES 40 and 42): the manifest checks of every cell's tests are
functions of a loaded manifest and its root, and pass on the benchmark's
own manifest with a sixth configuration and cell appended; every test of
this directory that reads the root's manifest is run again on a root that
holds such a cell on disk, as the root of a `model_config` PR does (the
list is collected from the tests' code, at the foot of this file); the
readers that several architectures share find their table and cost module
by the run's `model_type` and read the accepted cells as they did."""
import contextlib
import glob
import importlib
import inspect
import itertools
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import one_more_cell  # noqa: E402
import test_chipbench as every  # noqa: E402
import test_dots3_note_cell as dots3  # noqa: E402
import test_granite_cell as granite  # noqa: E402
import test_solar_open2_cell as solar  # noqa: E402
from chipbench import run as bench_run  # noqa: E402
from chipbench import scope_reduce, scope_tables  # noqa: E402


def _manifest(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def test_a_sixth_configuration_and_cell_pass_every_manifest_check(tmp_path):
    root = one_more_cell.copy_of(ROOT, tmp_path)
    name, new = one_more_cell.append_cell(root)
    m, before = _manifest(root), _manifest(ROOT)
    assert {w["name"] for w in m["workloads"]} == {new} | {
        w["name"] for w in before["workloads"]}
    assert {c["name"] for c in m["configs"]} == {name} | {
        c["name"] for c in before["configs"]}
    every.check_manifest(m, root, whole=True)
    for cell_tests in (granite, solar, dots3):
        cell_tests.check_manifest(m, root)
    # the new cell is a cell like any other: found by name, its files
    # loaded, every metric that lists it read by a reader that is there
    _, entry, cell_file, config, traffic = bench_run.load_cell(root, new)
    assert entry["config"] == name and traffic["seq_len"] == 32768
    assert config["model_type"] == "granitemoehybrid"
    listed = bench_run.metrics_of(m, "per_layer", new)
    assert {x["name"] for x in listed} >= set(granite.NEW) | set(granite.OLD)


def _runs():
    trace, counters = solar._hand_written()
    return {"solar_open2": solar._run(trace, counters),
            "granitemoehybrid": granite._run(granite._recorded()),
            "dots3_note": dots3._run(
                dots3._recorded(),
                counters={"expert_tokens": [[500, 524, 0, 512]] * 4,
                          "dropped_pairs": 0, "attended_pairs": [1]})}


# (reader, architecture) -> the table, group and cost module the PARENT's
# reader named in its own text
PARENT = {
    ("ssm_ms_per_step", "granitemoehybrid"): (
        "components_granitemoehybrid.json", "ssm", None),
    ("ssd_core_roofline", "granitemoehybrid"): (
        "components_granitemoehybrid.json", "ssd_core", "ssd_core_train"),
    ("ssm_conv_roofline", "granitemoehybrid"): (
        "components_granitemoehybrid.json", "ssm_conv", "ssm_conv_train"),
    ("moe_ms_per_step", "solar_open2"): (
        "components_solar_open2.json", "moe", None),
    ("moe_ms_per_step", "dots3_note"): (
        "components_solar_open2.json", "moe", None),
    ("moe_experts_roofline", "solar_open2"): (
        "components_solar_open2.json", "moe_experts", "moe_experts_train"),
}


@pytest.mark.parametrize("name,kind", sorted(PARENT))
def test_shared_readers_read_the_accepted_cells_as_the_parent_did(name, kind):
    """To the last digit: the value of the re-pointed reader is the value
    under the table and cost function the parent's reader named."""
    run = _runs()[kind]
    table, group, cost = PARENT[name, kind]
    value, note = bench_run.layer_metric(name).compute(run)
    if cost is None:
        want = scope_tables.ms_per_step(_runs()[kind], table, group)[0]
    else:
        mod = __import__("chipbench.costs_" + kind, fromlist=["x"])
        steps, cfg = run["steps_traced"], run["config"]
        if "moe" in name:
            work = [getattr(mod, cost)(cfg, sum(layer))
                    for layer in run["counters"]["expert_tokens"]]
        else:
            work = [getattr(mod, cost)(cfg, run["batch_size"],
                                       run["seq_len"])
                    ] * mod.sizes(cfg)["mamba"]
        want = scope_tables.roofline(
            _runs()[kind], table, group, steps * sum(w[0] for w in work),
            steps * sum(w[1] for w in work), "")[0]
    assert value == want and value > 0
    assert isinstance(note, str) and note


def test_readers_take_table_and_costs_from_the_runs_model_type():
    runs = _runs()
    first = "components_solar_open2.json"
    # a table of the run's own with the group: read through it
    assert scope_tables.table_of(runs["dots3_note"], "moe", first) == \
        "components_dots3_note.json"
    # the run's table lacks the group, or there is no such table: today's
    assert scope_tables.table_of(runs["dots3_note"], "moe_experts",
                                 first) == first
    assert scope_tables.table_of({"config": {"model_type": "llama"}},
                                 "moe", first) == first
    assert scope_tables.table_of({"config": None}, "moe", first) == first
    from chipbench import costs_dots3_note, costs_solar_open2
    assert scope_tables.costs_of(runs["dots3_note"], "moe_experts_train",
                                 "costs_solar_open2") is costs_dots3_note
    assert scope_tables.costs_of(runs["dots3_note"], "kda_core_train",
                                 "costs_solar_open2") is costs_solar_open2
    assert scope_tables.costs_of({"config": {"model_type": "llama"}},
                                 "ssd_core_train", "costs_granitemoehybrid"
                                 ).__name__.endswith("granitemoehybrid")
    # an architecture that brings no count of the state-space core
    for name in ("ssd_core_roofline", "ssm_conv_roofline"):
        assert bench_run.layer_metric(name).compute(
            runs["dots3_note"]) is None


def test_the_experts_roofline_reads_the_latent_attention_cell():
    """18 x pairs x H x M at H 5120, M 1536 and its bytes, through the
    run's own cost module; the device time through Solar's table, whose
    group `moe_experts` the run's own table lacks."""
    run = _runs()["dots3_note"]
    pairs = 500 + 524 + 512
    from chipbench import costs, costs_dots3_note as cd
    flops, byts = cd.moe_experts_train(run["config"], pairs)
    assert flops == 18 * pairs * 5120 * 1536
    assert byts == 2 * (9 * 8 * 5120 * 1536 + 5 * pairs * 5120)
    least, bound = costs.roofline_s(4 * flops, 4 * byts, run["peaks"])
    red, table = scope_tables.reduced(run, "components_solar_open2.json")
    spent = scope_reduce.group_s(red, "moe_experts", None, table)
    assert spent == pytest.approx(0.065)     # the small trace's moe/experts
    value, note = bench_run.layer_metric("moe_experts_roofline").compute(run)
    assert value == pytest.approx(100 * least / spent) and 0 < value < 100
    assert "pairs a layer" in note


# -- every test that reads the root's manifest, again with a sixth cell -------
#
# A test that opens `ROOT`'s BENCHMARK.json on its own escapes the manifest
# checks above (PR 41 met one that compared the whole manifest with `==`,
# and a `model_config` PR may not edit it). So the list is collected from
# the tests' code, and a test written tomorrow joins it unasked.

REAL = ROOT            # `run_on` points this module's `ROOT` elsewhere too
ACCEPTED = _manifest(REAL)
GROUPS = ("configs", "workloads", "end_to_end", "per_layer")
PARAM = type(pytest.param())


def _mentions(code):
    """(names, strings): the globals, attributes and arguments that
    `code` and the code nested in it name, and their string constants."""
    names = set(code.co_names) | set(code.co_varnames[:code.co_argcount])
    strings = set()
    for c in code.co_consts:
        if isinstance(c, types.CodeType):
            inner = _mentions(c)
            names |= inner[0]
            strings |= inner[1]
        elif isinstance(c, str):
            strings.add(c)
    return names, strings


def _reach(fn, seen):
    """`_mentions` of `fn` and of every function it names, as a global
    or as a fixture among its arguments, and so on from those."""
    fn = inspect.unwrap(fn) if callable(fn) else None
    if not inspect.isfunction(fn) or fn in seen:
        return set(), set()
    seen.add(fn)
    names, strings = _mentions(fn.__code__)
    for n in names & set(fn.__globals__):
        inner = _reach(fn.__globals__[n], seen)
        names, strings = names | inner[0], strings | inner[1]
    return names, strings


def reads_root(fn):
    """Whether `fn` may open its module's `ROOT` manifest: it, or what
    it calls by name, names both `ROOT` and "BENCHMARK.json". A test that
    reads another root's manifest and something else of `ROOT` is taken
    too: one run more, and no harm."""
    names, strings = _reach(fn, set())
    return "ROOT" in names and "BENCHMARK.json" in strings


def _from_root(value):
    """The group of the manifest that a `parametrize` value was taken
    from when the tests were collected, "root" for the root itself."""
    if isinstance(value, str) and value == REAL:
        return "root"
    if isinstance(value, dict):
        return next((g for g in GROUPS if value in ACCEPTED[g]), None)
    return None


def _word(value, otherwise):
    """A parameter's part of a case's id."""
    if _from_root(value) == "root":
        return "ROOT"
    if isinstance(value, dict) and "name" in value:
        return value["name"]
    if isinstance(value, (str, int, float)):
        return os.path.basename(str(value))
    return otherwise


def _cases_of(fn):
    """[(id, keyword arguments)] as `fn`'s `parametrize` marks give them:
    one case with no arguments where it has none."""
    axes = []
    for mark in getattr(fn, "pytestmark", []):
        if mark.name != "parametrize":
            continue
        names, rows = mark.args[0], []
        if isinstance(names, str):
            names = [n.strip() for n in names.split(",")]
        for i, v in enumerate(mark.args[1]):
            v = v.values if isinstance(v, PARAM) else (
                v if len(names) > 1 else (v,))
            rows.append(("-".join(_word(x, f"{names[0]}{i}") for x in v),
                         dict(zip(names, v))))
        axes.append(rows)
    return [("-".join(w for w, _ in combo),
             {k: v for _, kw in combo for k, v in kw.items()})
            for combo in itertools.product(*axes)]


def root_reading_tests(modules):
    """(id, module, test, keyword arguments) of every case of every test
    function of `modules` that reads the root's manifest: in its code
    (`reads_root`), or through a parameter taken from the root when the
    tests were collected (`_from_root`)."""
    found = []
    for mod in modules:
        for name, fn in sorted(vars(mod).items()):
            if not (name.startswith("test") and inspect.isfunction(fn)
                    and fn.__module__ == mod.__name__):
                continue
            in_code = reads_root(fn)
            for word, kwargs in _cases_of(fn):
                if in_code or any(map(_from_root, kwargs.values())):
                    found.append(pytest.param(mod, fn, kwargs, id="-".join(
                        filter(None, [f"{mod.__name__}.{name}", word]))))
    return found


def _fixture(mod, arg, request, stack):
    """`arg` as a test of `mod` gets it: from the module's own fixture,
    called here and finished on `stack`, or else from pytest (a test of
    this file cannot ask for another module's fixture by name)."""
    own = getattr(mod, arg, None)
    plain = inspect.unwrap(own) if callable(own) else None
    if plain is own or not inspect.isfunction(plain):
        return request.getfixturevalue(arg)
    got = plain(**{a: _fixture(mod, a, request, stack)
                   for a in inspect.signature(plain).parameters})
    if inspect.isgenerator(got):
        value = next(got)
        stack.callback(next, got, None)
        return value
    return got


def run_on(root, mod, fn, kwargs, request, monkeypatch, modules=()):
    """Call `fn(**kwargs)` with the `ROOT` of its module, and of every
    module of `modules` that has the real one, pointing at `root`, and
    with what the real root gave its parameters as `root` has it: the
    root itself, an entry of its manifest by name."""
    manifest = _manifest(root)
    for m in {mod, *modules}:
        if getattr(m, "ROOT", None) == REAL:
            monkeypatch.setattr(m, "ROOT", root)
    moved = {}
    for k, v in kwargs.items():
        group = _from_root(v)
        moved[k] = v if group is None else root if group == "root" else next(
            x for x in manifest[group] if x["name"] == v["name"])
    with contextlib.ExitStack() as stack:
        for arg in inspect.signature(fn).parameters:
            if arg not in moved:
                moved[arg] = _fixture(mod, arg, request, stack)
        fn(**moved)


@pytest.fixture(scope="module")
def sixth_root(tmp_path_factory):
    """A copy of the benchmark, code and data, with a sixth cell's files
    and entries: read only, the tests that write copy it first."""
    root = one_more_cell.copy_of(REAL, tmp_path_factory.mktemp("sixth"))
    one_more_cell.append_cell(root)
    return root


TOMORROW = """
import json, os, pytest
ROOT = {root!r}
def _manifest():
    return json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
def _cells():
    return {{w["name"] for w in _manifest()["workloads"]}}
def test_the_whole_set():
    assert _cells() == {cells!r}
def test_membership():
    assert _cells() >= {cells!r}
@pytest.mark.parametrize("entry", [
    x for x in _manifest()["per_layer"] if x["name"] == "ssm_ms_per_step"])
def test_an_entry_read_when_collected(entry):
    assert entry["workloads"] == {listed!r}
def test_of_something_else():
    assert os.path.exists(ROOT)
"""


def test_a_test_that_pins_the_whole_manifest_is_found_and_turns_red(
        sixth_root, request, monkeypatch):
    mod = types.ModuleType("tomorrow")
    exec(TOMORROW.format(
        root=REAL, cells={w["name"] for w in ACCEPTED["workloads"]},
        listed=next(x["workloads"] for x in ACCEPTED["per_layer"]
                    if x["name"] == "ssm_ms_per_step")), mod.__dict__)
    found = {p.id: p.values for p in root_reading_tests([mod])}
    assert set(found) == {
        "tomorrow.test_the_whole_set", "tomorrow.test_membership",
        "tomorrow.test_an_entry_read_when_collected-ssm_ms_per_step"}
    for _, fn, kwargs in found.values():
        fn(**kwargs)                                # all hold on the root
    run_on(sixth_root, *found["tomorrow.test_membership"], request,
           monkeypatch)
    for pinned in set(found) - {"tomorrow.test_membership"}:
        with pytest.raises(AssertionError):
            run_on(sixth_root, *found[pinned], request, monkeypatch)


def _test_modules():
    """Every test module of this directory, this one as far as it is
    defined (whatever name pytest imported it under)."""
    me = os.path.splitext(os.path.basename(__file__))[0]
    names = sorted(os.path.splitext(os.path.basename(p))[0]
                   for p in glob.glob(os.path.join(HERE, "test_*.py")))
    return [sys.modules[__name__] if n == me else importlib.import_module(n)
            for n in names]


MODULES = _test_modules()
# of this file, the tests above this line: the test that runs the list
# reads the root through every case, and would run itself
CASES = root_reading_tests(MODULES)


@pytest.mark.parametrize("mod,fn,kwargs", CASES)
def test_root_reading_tests_hold_with_a_sixth_cell_on_disk(
        mod, fn, kwargs, sixth_root, request, monkeypatch):
    run_on(sixth_root, mod, fn, kwargs, request, monkeypatch, MODULES)


def test_the_list_holds_a_test_of_every_file_that_reads_the_root():
    """The files ISSUE 42 read on the parent: a collector gone blind would
    leave the test above green with nothing to run."""
    assert {p.values[0].__name__ for p in CASES} >= {
        "test_chipbench", "test_per_step_limits", "test_step_memory_readers",
        "test_scope_metrics", "test_granite_cell", "test_solar_open2_cell",
        "test_dots3_note_cell", "test_sixth_cell"}
