"""paddle_tpu.analysis.dataflow — def-use chains, liveness, aliasing,
effects, and the two planes built on them: the donation-safety proof
(L011 + Executor auto-downgrade) and lints L010/L012 with full
nested-block-path citations.

Tier-1 (JAX_PLATFORMS=cpu safe).  Also the home of the satellite gates:
the tree-clean sweep over every in-repo example/benchmark Program, the
``lint --format=json`` schema round-trip, the randomized shape-interpreter
vs ``jax.eval_shape`` cross-check, and the verify=True perf budget.
"""

import json
import os
import sys
import time
import warnings

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import paddle_tpu.analysis as A
import paddle_tpu.fluid as fluid
from paddle_tpu.analysis import dataflow as DF
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.registry import OpRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_programs():
    fluid.reset_default_programs()
    fluid.executor._global_scope = fluid.Scope()
    yield


def _codes(diags):
    return [d.code for d in diags]


# ---------------------------------------------------------------- builders --

def _read_after_donate_program():
    """The seeded hazard: v aliases persistable w (reshape view), sgd
    overwrites w in place, then v is read — the read may observe the
    post-update buffer if w's buffer were donated."""
    prog = fluid.default_main_program()
    b = prog.global_block()
    w = b.create_var(name="w", shape=[4], dtype="float32", persistable=True)
    x = layers.data(name="x", shape=[4], dtype="float32")
    v = b.create_var(name="v", shape=[4], dtype="float32")
    b.append_op("reshape", {"X": [w.name]}, {"Out": [v.name]},
                {"shape": [4]})
    g = b.create_var(name="g", shape=[4], dtype="float32")
    b.append_op("fill_constant", {}, {"Out": [g.name]},
                {"shape": [4], "dtype": "float32", "value": 1.0})
    lr = b.create_var(name="lr", shape=[1], dtype="float32")
    b.append_op("fill_constant", {}, {"Out": [lr.name]},
                {"shape": [1], "dtype": "float32", "value": 0.1})
    b.append_op("sgd", {"Param": [w.name], "Grad": [g.name],
                        "LearningRate": [lr.name]},
                {"ParamOut": [w.name]}, {"learning_rate": 0.1})
    z = b.create_var(name="z", shape=[4], dtype="float32")
    b.append_op("elementwise_add", {"X": [v.name], "Y": [x.name]},
                {"Out": [z.name]}, {})
    return prog, b, z


def _train_program():
    x = layers.data(name="x", shape=[8], dtype="float32")
    y = layers.fc(input=x, size=4)
    loss = layers.mean(y)
    fluid.AdamOptimizer(1e-3).minimize(loss)
    return fluid.default_main_program(), loss


# --------------------------------------------------- def-use chain building --

def test_def_use_chain_and_entry_defs():
    prog, b, z = _read_after_donate_program()
    df = A.analyze_dataflow(prog, fetch=[z.name])
    # w: entry def + the sgd overwrite
    defs_w = df.defs_of("w")
    assert [d.kind for d in defs_w] == ["entry", "op"]
    assert defs_w[1].op_type == "sgd"
    # v's single def roots back to w's ENTRY def (view aliasing)
    (dv,) = [d for d in df.defs_of("v") if d.kind == "op"]
    assert df.entry_defs["w"] in dv.roots
    # v is read once, by the add, and that read reaches only dv
    (uv,) = df.uses_of("v")
    assert uv.op_type == "elementwise_add" and uv.defs == {dv}
    # the sgd's own read of w reaches the ENTRY def, not its own output
    reads_w = [u for u in df.uses_of("w") if u.op_type == "sgd"]
    assert reads_w and all(defs_w[0] in u.defs for u in reads_w)


def test_effect_classification():
    prog, b, z = _read_after_donate_program()
    df = A.analyze_dataflow(prog, fetch=[z.name])
    eff = {b.ops[i].type: df.effects[(0, i)] for i in range(len(b.ops))}
    assert eff["reshape"] == A.Effect.PURE
    assert eff["fill_constant"] == A.Effect.PURE
    assert eff["elementwise_add"] == A.Effect.PURE
    assert eff["sgd"] == A.Effect.INPLACE


def test_effect_classification_control_and_side_effect():
    i = layers.fill_constant(shape=[1], dtype="int64", value=0)
    n = layers.fill_constant(shape=[1], dtype="int64", value=2)
    cond = layers.less_than(i, n)
    with fluid.While(cond).block():
        layers.increment(i)
        layers.less_than(i, n, cond=cond)
    b = fluid.default_main_program().global_block()
    r = b.create_var(shape=[3], dtype="float32")
    b.append_op("gaussian_random", {}, {"Out": [r.name]},
                {"shape": [3], "mean": 0.0, "std": 1.0, "seed": 7,
                 "dtype": "float32"})
    df = A.analyze_dataflow(fluid.default_main_program())
    by_type = {b.ops[i].type: df.effects[(0, i)] for i in range(len(b.ops))}
    assert by_type["while"] == A.Effect.CONTROL
    assert by_type["gaussian_random"] == A.Effect.SIDE_EFFECT


def test_explain_var_chain_text():
    prog, b, z = _read_after_donate_program()
    df = A.analyze_dataflow(prog, fetch=[z.name])
    s = A.explain_var(df, "w")
    assert "defined on entry" in s
    assert "redefined at block 0, op #3 (sgd)" in s
    s2 = A.explain_var(df, "v")
    assert "defined at block 0, op #0 (reshape)" in s2
    assert "last read at block 0, op #4 (elementwise_add)" in s2
    assert A.explain_var(df, "no_such_var") is None


# --------------------------------------------------- donation-safety proof --

def test_donation_hazard_detected_with_sites():
    prog, b, z = _read_after_donate_program()
    hz = A.donation_hazards(prog, fetch=[z.name])
    assert [h.name for h in hz] == ["w"]
    msg = hz[0].describe()
    assert "overwritten at block 0, op #3 (sgd)" in msg
    assert "read at block 0, op #4 (elementwise_add) via alias 'v'" in msg


def test_training_program_proves_donation_safe():
    """The critical no-false-positive baseline: a real fc+Adam training
    step donates every parameter and the proof must go through — Adam's
    reads of the OLD parameter values all happen before (or at) the
    in-place update, and nothing reads them afterwards."""
    prog, loss = _train_program()
    assert A.donation_hazards(prog, fetch=[loss.name]) == []


def test_verify_true_refuses_read_after_donate():
    prog, b, z = _read_after_donate_program()
    exe = fluid.Executor()
    exe.scope.set("w", np.arange(4, dtype=np.float32))
    feed = {"x": np.zeros(4, dtype=np.float32)}
    with pytest.raises(A.ProgramVerificationError) as ei:
        exe.run(prog, feed=feed, fetch_list=[z], verify=True, donate=True)
    s = str(ei.value)
    assert "L011" in s
    # the refusal cites both the overwrite (def) and the stale read (use)
    assert "block 0, op #3 (sgd)" in s
    assert "block 0, op #4 (elementwise_add)" in s


def test_verify_true_donation_off_only_warns():
    """Same program, donation off: the hazard is advisory (donation is a
    run-time switch), so verify must NOT refuse."""
    prog, b, z = _read_after_donate_program()
    exe = fluid.Executor()
    exe.scope.set("w", np.arange(4, dtype=np.float32))
    out, = exe.run(prog, feed={"x": np.zeros(4, np.float32)},
                   fetch_list=[z], verify=True, donate=False)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.arange(4, dtype=np.float32))


def test_executor_auto_downgrades_hazardous_donation():
    """verify=False + donate=True: the Executor must not corrupt values —
    it downgrades the hazardous persistable to keep, warns once naming
    L011, and produces bit-identical results to donate=False."""
    feed = {"x": np.zeros(4, dtype=np.float32)}

    def run(donate):
        fluid.reset_default_programs()
        fluid.executor._global_scope = fluid.Scope()
        prog, b, z = _read_after_donate_program()
        exe = fluid.Executor()
        exe.scope.set("w", np.arange(4, dtype=np.float32))
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            out, = exe.run(prog, feed=feed, fetch_list=[z], verify=False,
                           donate=donate)
            # second run (same scope state): the warning is once-per-program
            exe.scope.set("w", np.arange(4, dtype=np.float32))
            out2, = exe.run(prog, feed=feed, fetch_list=[z], verify=False,
                            donate=donate)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
        l011 = [w for w in rec if "L011" in str(w.message)]
        return np.asarray(out), l011

    donated, warned = run(True)
    kept, not_warned = run(False)
    assert np.array_equal(donated, kept)
    # z = reshape(w_old) + 0 — the pre-update value, proving no corruption
    np.testing.assert_array_equal(donated, np.arange(4, dtype=np.float32))
    assert len(warned) == 1 and "'w'" in str(warned[0].message)
    assert not_warned == []


def test_safe_training_program_keeps_donation():
    """The downgrade must not fire on provably-safe programs: a training
    step's params stay donated (no L011 warning) and training works."""
    prog, loss = _train_program()
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    feed = {"x": np.ones((2, 8), np.float32)}
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        l0, = exe.run(prog, feed=feed, fetch_list=[loss], verify=True,
                      donate=True)
        l1, = exe.run(prog, feed=feed, fetch_list=[loss], verify=True,
                      donate=True)
    assert not [w for w in rec if "L011" in str(w.message)]
    assert float(np.asarray(l1)) != float(np.asarray(l0))  # params moved


# ------------------------------------------------------- lints L010 / L012 --

def test_l010_dead_write_cross_sub_block():
    """An outer write killed inside a sub-block (and vice versa) is L010's
    domain — V003 owns same-block duplicate writes."""
    t = layers.fill_constant(shape=[1], dtype="float32", value=1.0)
    i = layers.fill_constant(shape=[1], dtype="int64", value=0)
    n = layers.fill_constant(shape=[1], dtype="int64", value=2)
    cond = layers.less_than(i, n)
    with fluid.While(cond).block():
        layers.assign(layers.fill_constant(shape=[1], dtype="float32",
                                           value=2.0), t)
        layers.increment(i)
        layers.less_than(i, n, cond=cond)
    # this post-loop overwrite kills BOTH earlier writes on every path —
    # the pre-loop fill (killed in the sub-block first) and the loop-body
    # assign; each is a cross-block dead write, which is L010's domain
    layers.assign(layers.fill_constant(shape=[1], dtype="float32",
                                       value=3.0), t)
    out = layers.relu(t)
    diags = A.analyze_program(fluid.default_main_program(),
                              fetch=[out.name])
    l010 = [d for d in diags if d.code == "L010"]
    assert l010, A.format_diagnostics(diags)
    # the finding cites the killing write's full nested path
    assert any("block 0.1" in d.message for d in l010), \
        A.format_diagnostics(l010)


def test_no_l010_on_loop_carried_state():
    """Loop counters/accumulators are written every iteration and read on
    the NEXT one (back edge): never dead."""
    i = layers.fill_constant(shape=[1], dtype="int64", value=0)
    n = layers.fill_constant(shape=[1], dtype="int64", value=3)
    acc = layers.fill_constant(shape=[1], dtype="float32", value=0.0)
    cond = layers.less_than(i, n)
    with fluid.While(cond).block():
        layers.assign(layers.elementwise_add(acc, acc), acc)
        layers.increment(i)
        layers.less_than(i, n, cond=cond)
    diags = A.analyze_program(fluid.default_main_program(),
                              fetch=[acc.name])
    assert not [d for d in diags if d.code in ("L010", "L012")], \
        A.format_diagnostics(diags)


def test_l012_alias_escape_from_sub_block():
    """A sub-block op that rebinds a VIEW of an outer var into a fresh
    name leaks aliasing across the scope boundary."""
    m = layers.fill_constant(shape=[4], dtype="float32", value=1.0)
    i = layers.fill_constant(shape=[1], dtype="int64", value=0)
    n = layers.fill_constant(shape=[1], dtype="int64", value=2)
    cond = layers.less_than(i, n)
    with fluid.While(cond).block():
        v = layers.reshape(m, shape=[2, 2])
        s = layers.reduce_sum(v)
        b = fluid.default_main_program().current_block()
        fresh = b.create_var(shape=[2, 2], dtype="float32")
        b.append_op("assign", {"X": [v.name]}, {"Out": [fresh.name]}, {})
        del s  # read site for v exists; its value is otherwise unused
        layers.increment(i)
        layers.less_than(i, n, cond=cond)
    diags = A.analyze_program(fluid.default_main_program())
    l012 = [d for d in diags if d.code == "L012"]
    assert l012, A.format_diagnostics(diags)
    assert l012[0].severity == A.Severity.WARNING
    assert "block 0.1" in (l012[0].block_path or "") or \
        l012[0].block_path == "0.1"


def test_l011_advisory_without_donate_flag():
    """Static lint (donate unknown): the hazard is a WARNING with the
    advisory qualifier; with donate=True it is an ERROR."""
    prog, b, z = _read_after_donate_program()
    advisory = [d for d in A.analyze_program(prog, fetch=[z.name])
                if d.code == "L011"]
    assert advisory and advisory[0].severity == A.Severity.WARNING
    assert "advisory" in advisory[0].message
    hard = [d for d in A.analyze_program(prog, fetch=[z.name], donate=True)
            if d.code == "L011"]
    assert hard and hard[0].severity == A.Severity.ERROR
    off = [d for d in A.analyze_program(prog, fetch=[z.name], donate=False)
           if d.code == "L011"]
    assert off == []


def test_dataflow_lints_gated_by_structural_errors():
    """L010-L012 reason over sub-block indices the verifier validates —
    with V0xx errors present they must not fire (garbage chains)."""
    b = fluid.default_main_program().global_block()
    out = b.create_var(shape=[4], dtype="float32")
    b.append_op("elementwise_add", {"X": ["ghost"], "Y": ["ghost2"]},
                {"Out": [out.name]}, {})
    diags = A.analyze_program(fluid.default_main_program())
    assert A.errors(diags)
    assert not [d for d in diags if d.code in ("L010", "L011", "L012")]


# -------------------------------------------- nested block-path diagnostics --

def test_lint_catalogue_has_l010_l011_l012():
    assert A.LINT_CATALOGUE["L010"] == ("dead-write", A.Severity.WARNING)
    assert A.LINT_CATALOGUE["L011"] == ("donation-hazard", A.Severity.ERROR)
    assert A.LINT_CATALOGUE["L012"] == ("alias-escape", A.Severity.WARNING)


def test_block_paths_nested_chain():
    i = layers.fill_constant(shape=[1], dtype="int64", value=0)
    n = layers.fill_constant(shape=[1], dtype="int64", value=2)
    cond = layers.less_than(i, n)
    with fluid.While(cond).block():
        j = layers.fill_constant(shape=[1], dtype="int64", value=0)
        m = layers.fill_constant(shape=[1], dtype="int64", value=2)
        cond2 = layers.less_than(j, m)
        with fluid.While(cond2).block():
            layers.increment(j)
            layers.less_than(j, m, cond=cond2)
        layers.increment(i)
        layers.less_than(i, n, cond=cond)
    prog = fluid.default_main_program()
    paths = A.block_paths(prog)
    assert paths[0] == "0"
    inner = [p for p in paths.values() if p.count(".") == 2]
    assert inner and all(p.startswith("0.") for p in inner)
    # root sites keep the historical format; nested cite the chain
    assert A.op_site(0, 3, "concat", block_path=paths[0]) \
        == "block 0, op #3 (concat)"
    bidx = [b for b, p in paths.items() if p.count(".") == 2][0]
    assert A.op_site(bidx, 0, "increment", block_path=paths[bidx]) \
        == f"block {paths[bidx]}, op #0 (increment)"


def test_runtime_trace_error_cites_nested_path():
    i = layers.fill_constant(shape=[1], dtype="int64", value=0)
    n = layers.fill_constant(shape=[1], dtype="int64", value=3)
    acc = layers.fill_constant(shape=[2], dtype="float32", value=0.0)
    cond = layers.less_than(i, n)
    with fluid.While(cond).block():
        bad = layers.reshape(acc, shape=[7])     # 2 -> 7 fails in trace
        layers.assign(bad, acc)
        layers.increment(i)
        layers.less_than(i, n, cond=cond)
    exe = fluid.Executor()
    with pytest.raises(Exception) as ei:
        exe.run(fluid.default_main_program(), fetch_list=[acc],
                verify=False)
    notes = "\n".join(getattr(ei.value, "__notes__", []) or [str(ei.value)])
    assert "block 0.1, op #0 (reshape)" in notes


# ----------------------------------------------------- tree-clean lint gate --

# every in-repo example; script-style ones (no module-level `cost` config
# contract) are explicitly waived WITH the reason — additions to examples/
# without a waiver must lint clean
EXAMPLE_WAIVERS = {
    "gan_vae_mnist.py": "script-style (builds programs inside main())",
    "machine_translation.py": "script-style (imperative train/infer flow)",
    "model_zoo_features.py": "script-style feature tour, no single config",
    "serving_llm.py": "script-style serving daemon, no training config",
    "README.md": "not a Python config",
}


def _tree_examples():
    return sorted(os.listdir(os.path.join(REPO, "examples")))


def test_every_example_linted_or_waived():
    for name in _tree_examples():
        assert name.endswith(".py") or name in EXAMPLE_WAIVERS
    stale = set(EXAMPLE_WAIVERS) - set(_tree_examples())
    assert not stale, f"waivers for deleted examples: {stale}"


@pytest.mark.parametrize("name", [n for n in sorted(os.listdir(
    os.path.join(REPO, "examples"))) if n not in EXAMPLE_WAIVERS])
def test_example_tree_clean(name, capsys):
    from paddle_tpu import cli
    rc = cli.main(["lint", "--config",
                   os.path.join(REPO, "examples", name)])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "0 error(s)" in out


def test_benchmark_program_tree_clean():
    """benchmarks/fluid_executor.py's MLP training Program (replicated —
    the benchmark builds it inside run()); the only benchmark that goes
    through Program IR.  Zero findings, including L010-L012."""
    img = layers.data("img", shape=(784,))
    label = layers.data("label", shape=(), dtype="int32")
    h1 = layers.fc(img, 256, act="relu")
    h2 = layers.fc(h1, 64, act="relu")
    logits = layers.fc(h2, 10)
    loss = layers.mean(
        layers.softmax_with_cross_entropy(logits, label))
    fluid.AdamOptimizer(1e-3).minimize(loss)
    for prog, fetch in ((fluid.default_main_program(), [loss.name]),
                        (fluid.default_startup_program(), [])):
        diags = A.analyze_program(prog, fetch=fetch, donate=True)
        assert not diags, A.format_diagnostics(diags)


# ------------------------------------------------ lint --format=json schema --

def test_lint_format_json_schema_roundtrip(capsys, tmp_path):
    from paddle_tpu import cli
    rc = cli.main(["lint", "--config",
                   os.path.join(REPO, "examples", "fit_a_line.py"),
                   "--format=json", "--explain"])
    out = capsys.readouterr().out
    payload = json.loads(out)          # stdout is PURE json
    assert rc == 0
    assert payload["version"] == 1
    assert set(payload) == {"version", "findings", "summary"}
    assert set(payload["summary"]) == {"errors", "warnings", "info",
                                       "total"}
    assert payload["summary"]["errors"] == 0
    for f in payload["findings"]:
        assert set(f) == {"code", "severity", "message", "hint",
                          "explain", "site"}
        assert set(f["site"]) == {"program", "block", "block_path", "op",
                                  "op_type", "var"}
    # round-trip: re-serialize identically (stable key order)
    assert json.loads(json.dumps(payload, sort_keys=True)) == payload


def test_lint_format_json_findings_sites(capsys, tmp_path):
    """A config with a real finding: the JSON site block carries the
    nested path and --explain fills the chain."""
    cfg = tmp_path / "dead_cfg.py"
    cfg.write_text(
        "import paddle_tpu.fluid as fluid\n"
        "from paddle_tpu.fluid import layers\n"
        "x = layers.data('x', shape=(4,))\n"
        "unused = layers.data('unused', shape=(4,))\n"
        "dead = layers.relu(x)\n"   # never read, not fetched
        "cost = layers.mean(x)\n")
    from paddle_tpu import cli
    rc = cli.main(["lint", "--config", str(cfg), "--format=json",
                   "--explain", "--fail-on", "warning"])
    out = capsys.readouterr().out
    payload = json.loads(out)
    assert rc == 1
    findings = payload["findings"]
    assert findings and payload["summary"]["total"] == len(findings)
    flagged = [f for f in findings if f["site"]["var"]]
    assert flagged
    assert any(f["explain"] for f in flagged)


def test_lint_exit_code_contract(capsys, tmp_path):
    from paddle_tpu import cli
    # 2: usage error (unloadable config)
    rc = cli.main(["lint", "--config", str(tmp_path / "missing.py")])
    capsys.readouterr()
    assert rc == 2
    # 0: clean
    rc = cli.main(["lint", "--config",
                   os.path.join(REPO, "examples", "fit_a_line.py")])
    capsys.readouterr()
    assert rc == 0


# ------------------------------------- property test: shapes vs eval_shape --

_PROP_UNARY = ["relu", "tanh", "sigmoid", "square"]
_PROP_BINARY = ["elementwise_add", "elementwise_mul", "elementwise_sub"]


def _random_program(rs):
    """A random straight-line program over the core op vocabulary; returns
    (program, {feed name: concrete array})."""
    batch = int(rs.randint(1, 5))
    width = int(rs.randint(2, 7))
    x = layers.data(name="px", shape=[width], dtype="float32")
    b = fluid.default_main_program().global_block()
    feeds = {"px": rs.randn(batch, width).astype(np.float32)}
    avail = [("px", width)]
    for k in range(int(rs.randint(2, 7))):
        name, w = avail[rs.randint(len(avail))]
        kind = rs.randint(5)
        out = b.create_var(shape=[-1, w], dtype="float32")
        if kind == 0:
            b.append_op(_PROP_UNARY[rs.randint(len(_PROP_UNARY))],
                        {"X": [name]}, {"Out": [out.name]}, {})
            avail.append((out.name, w))
        elif kind == 1:
            other = [n for n, ww in avail if ww == w]
            rhs = other[rs.randint(len(other))]
            b.append_op(_PROP_BINARY[rs.randint(len(_PROP_BINARY))],
                        {"X": [name], "Y": [rhs]},
                        {"Out": [out.name]}, {})
            avail.append((out.name, w))
        elif kind == 2:
            w2 = int(rs.randint(2, 7))
            wm = b.create_var(shape=[w, w2], dtype="float32",
                              persistable=True)
            out2 = b.create_var(shape=[-1, w2], dtype="float32")
            b.append_op("matmul", {"X": [name], "Y": [wm.name]},
                        {"Out": [out2.name]}, {})
            avail.append((out2.name, w2))
        elif kind == 3:
            out2 = b.create_var(shape=[-1], dtype="float32")
            b.append_op("reduce_sum", {"X": [name]}, {"Out": [out2.name]},
                        {"dim": [1], "keep_dim": False})
        else:
            out2 = b.create_var(shape=[-1, w], dtype="float16")
            b.append_op("cast", {"X": [name]}, {"Out": [out2.name]},
                        {"dtype": "float16"})
    return fluid.default_main_program(), feeds


@pytest.mark.parametrize("seed", range(8))
def test_shape_interpreter_matches_eval_shape(seed):
    """Randomized cross-check: for every var the interpreter resolves, its
    (shape, dtype) must equal jax.eval_shape of the actual op computes."""
    rs = np.random.RandomState(seed)
    prog, feeds = _random_program(rs)
    block = prog.blocks[0]
    env, diags = A.infer_program_shapes(
        prog, feed_shapes={k: (v.shape, v.dtype.name)
                           for k, v in feeds.items()})
    assert not A.errors(diags), A.format_diagnostics(diags)

    # ground truth: eval_shape the op computes over abstract inputs
    truth = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in feeds.items()}
    for name, v in block.vars.items():
        if v.persistable:
            truth[name] = jax.ShapeDtypeStruct(
                tuple(v.shape), np.dtype(v.dtype))
    for op in block.ops:
        compute = OpRegistry.get(op.type)
        ins = {k: [truth[n] for n in vs] for k, vs in op.inputs.items()}
        outs = jax.eval_shape(lambda i, c=compute, a=dict(op.attrs):
                              c(i, a), ins)
        for k, names in op.outputs.items():
            for n, s in zip(names, outs[k]):
                truth[n] = s

    checked = 0
    for name, s in env.items():
        if s is A.UNKNOWN or name not in truth:
            continue
        if any(d < 0 for d in getattr(s, "shape", ())):
            continue
        assert tuple(s.shape) == tuple(truth[name].shape), name
        assert np.dtype(s.dtype) == np.dtype(truth[name].dtype), name
        checked += 1
    assert checked >= len(block.ops) // 2  # the check has teeth


# --------------------------------------------------------------- perf budget --

@pytest.mark.perf
def test_verify_preflight_fits_wall_budget():
    """verify=True pre-flight (structural + shapes + dataflow + lints)
    over a GPT-2-small-sized Program must stay interactive.  Budget is
    generous vs CI jitter but catches accidental quadratic blowups."""
    x = layers.data(name="x", shape=[768], dtype="float32")
    h = x
    for _ in range(12):
        m = layers.fc(h, 3072, act="gelu")
        o = layers.fc(m, 768)
        h = layers.elementwise_add(o, h)
        h = layers.activation(h, "tanh")
    loss = layers.mean(h)
    fluid.AdamOptimizer(1e-4).minimize(loss)
    prog = fluid.default_main_program()
    n_ops = sum(len(b.ops) for b in prog.blocks)
    assert n_ops > 120, n_ops     # really GPT-2-small sized

    t0 = time.perf_counter()
    diags = A.check_or_raise(prog, fetch=[loss.name], donate=True)
    elapsed = time.perf_counter() - t0
    assert not A.errors(diags)
    # also prove the dataflow piece alone is cheap enough to re-run
    t1 = time.perf_counter()
    df = A.analyze_dataflow(prog, fetch=[loss.name])
    hz = A.donation_hazards(prog, df=df)
    dflow = time.perf_counter() - t1
    assert hz == []
    budget = float(os.environ.get("PADDLE_TPU_VERIFY_BUDGET_S", "20"))
    assert elapsed + dflow < budget, (elapsed, dflow)
