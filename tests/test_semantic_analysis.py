"""graftsem (ISSUE 14 tentpole): the semantic tier's tier-1 gate plus
per-checker fixtures.

The mirror of test_analysis.py, one tier up:

- THE GATE: the shipped contract registry lowers clean on the tier-1
  CPU backend — zero findings, zero import errors, nothing degraded —
  and the lowering evidence pins the invariants that used to be
  checkable only dynamically: the LM fresh/steady/restored triple
  collapses to ONE executable (the PR-4 bug class, now a lint), the
  serving plan compiles exactly one executable per canonical bucket,
  and the distributed GBDT paths show real (non-vacuous) all-reduce
  traffic inside their declared budgets.
- FIXTURES: every checker is proven to (a) flag a seeded violation in
  a synthetic contract module and (b) honor the standard
  `# graftlint: disable=semantic.<rule>` comment on the decorator
  line, so the gate can never go green because a checker silently
  stopped firing.
"""
import itertools
import json
import os
import sys
import textwrap

import pytest

from mmlspark_tpu.analysis import BASELINE_FILENAME, Baseline, Finding
from mmlspark_tpu.analysis.semantic import SEMANTIC_RULES, run_semantic

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_seq = itertools.count()


def _run_fixture(tmp_path, monkeypatch, body, attr="contract"):
    """Write a synthetic contract module under tmp_path, register it as
    the ONLY entrypoint, and run the semantic tier over it."""
    name = f"_semfix_{next(_seq)}"
    (tmp_path / f"{name}.py").write_text(textwrap.dedent(body))
    monkeypatch.syspath_prepend(str(tmp_path))
    try:
        return run_semantic(root=str(tmp_path), entrypoints=[(name, attr)])
    finally:
        sys.modules.pop(name, None)


# ------------------------------------------------------------- the gate
@pytest.fixture(scope="module")
def shipped():
    """One lowering pass over the shipped registry, shared by the gate
    and the evidence pins below."""
    return run_semantic(root=_REPO)


def test_shipped_registry_is_semantically_clean(shipped):
    assert not shipped.errors, "\n".join(repr(f) for f in shipped.errors)
    assert not shipped.findings, "\n".join(
        repr(f) for f in shipped.findings)
    assert len(shipped.contracts) >= 6, shipped.contracts
    for cname, ev in shipped.stats.items():
        # per-field degradation is allowed by the never-raise contract,
        # but on the tier-1 CPU backend the chain must complete: a
        # degraded field here means a checker just went vacuous
        assert not ev["degraded"], (cname, ev["degraded"])
        for case, basis in ev["fingerprint_basis"].items():
            assert basis == "compiled", (cname, case, basis)


def test_lm_step_is_one_executable_across_restore(shipped):
    # the PR-4 invariant as a lint: fresh-layout, steady-state, and
    # checkpoint-restored arguments must all hit the SAME executable
    ev = shipped.stats["lm.step"]
    assert sorted(ev["cases"]) == ["fresh", "restored", "steady"]
    assert ev["distinct_executables"] == 1, ev["fingerprints"]


def test_serving_plan_compiles_once_per_bucket(shipped):
    ev = shipped.stats["serving.plan"]
    fps = ev["fingerprints"]
    for b in (8, 16, 32):
        # a repeat request in the same canonical bucket must not
        # recompile — fresh and repeat collapse to one fingerprint
        assert fps[f"bucket{b}-fresh"] == fps[f"bucket{b}-repeat"], fps
    assert ev["distinct_executables"] == 3, fps


def test_distributed_collective_check_is_not_vacuous(shipped):
    # the 8-virtual-device CPU mesh must lower REAL all-reduces into
    # the optimized module, or the budget checker is checking nothing
    for cname in ("gbdt.tree.distributed", "gbdt.vote.distributed",
                  "gbdt.chunk.distributed"):
        for case, kinds in shipped.stats[cname]["collectives"].items():
            assert kinds.get("all-reduce", {}).get("ops", 0) >= 1, (
                cname, case, kinds)


def test_single_device_paths_are_collective_free(shipped):
    for cname in ("gbdt.chunk.fused", "gbdt.hist.kernel"):
        ev = shipped.stats[cname]
        assert ev["distinct_executables"] == 1, ev["fingerprints"]
        for case, kinds in ev["collectives"].items():
            assert kinds == {}, (cname, case, kinds)


# ------------------------------------- checker fixtures (flag + suppress)
_IDENTITY_SRC = """
import jax.numpy as jnp
from mmlspark_tpu.analysis.semantic import Case, hot_path_contract

@hot_path_contract("fix.identity"){disable}
def contract():
    def f(x):
        return x * 2.0
    return [Case("small", f, (jnp.zeros((4,), jnp.float32),)),
            Case("large", f, (jnp.zeros((8,), jnp.float32),))]
"""


def test_executable_identity_flags_and_suppresses(tmp_path, monkeypatch):
    rep = _run_fixture(tmp_path, monkeypatch,
                       _IDENTITY_SRC.format(disable=""))
    assert not rep.errors, rep.errors
    assert [f.rule for f in rep.findings] == [
        "semantic.executable-identity"], rep.findings
    assert "2 distinct executables" in rep.findings[0].message
    assert rep.findings[0].tier == "semantic"
    rep2 = _run_fixture(
        tmp_path, monkeypatch, _IDENTITY_SRC.format(
            disable="  # graftlint: disable=semantic.executable-identity"))
    assert rep2.findings == [] and not rep2.errors


_DONATION_SRC = """
import jax.numpy as jnp
from mmlspark_tpu.analysis.semantic import Case, hot_path_contract

@hot_path_contract({disable}
    "fix.donation", expected_executables=2,
    donate_expected=(0,), reused_after_step=(1,))
def contract():
    def f(state, x):
        return state + x, x * 2.0
    state = jnp.zeros((64,), jnp.float32)
    x = jnp.ones((64,), jnp.float32)
    return [Case("nodonate", f, (state, x), group="plain"),
            Case("overdonate", f, (state, x), group="donating",
                 jit_kwargs=dict(donate_argnums=(0, 1)))]
"""


def test_donation_flags_and_suppresses(tmp_path, monkeypatch):
    rep = _run_fixture(tmp_path, monkeypatch,
                       _DONATION_SRC.format(disable=""))
    assert not rep.errors, rep.errors
    msgs = [f.message for f in rep.findings]
    assert all(f.rule == "semantic.donation" for f in rep.findings), msgs
    assert any("not donated" in m for m in msgs), msgs          # missing
    assert any("not declared" in m for m in msgs), msgs         # extra
    assert any("use-after-donation" in m for m in msgs), msgs   # reused
    rep2 = _run_fixture(
        tmp_path, monkeypatch, _DONATION_SRC.format(
            disable="  # graftlint: disable=semantic.donation"))
    assert rep2.findings == [] and not rep2.errors


_HOST_SYNC_SRC = """
import jax
import jax.numpy as jnp
from mmlspark_tpu.analysis.semantic import Case, hot_path_contract

@hot_path_contract({disable}
    "fix.hostsync", host_fetch_outputs=(-1,),
    max_host_transfer_bytes={cap}{allow})
def contract():
    def noisy(x):
        jax.debug.print("x0={{v}}", v=x[0])
        return x * 2.0, x + 1.0
    return [Case("noisy", noisy, (jnp.zeros((64,), jnp.float32),))]
"""


def test_host_sync_flags_and_suppresses(tmp_path, monkeypatch):
    rep = _run_fixture(tmp_path, monkeypatch,
                       _HOST_SYNC_SRC.format(disable="", allow="", cap=8))
    assert not rep.errors, rep.errors
    msgs = [f.message for f in rep.findings]
    assert all(f.rule == "semantic.host-sync" for f in rep.findings), msgs
    assert any("debug_callback" in m for m in msgs), msgs
    # host_fetch_outputs=(-1,) must resolve python-style to the LAST
    # output (256 B > the 8 B cap), not be silently skipped
    assert any("256 bytes" in m for m in msgs), msgs
    rep2 = _run_fixture(
        tmp_path, monkeypatch, _HOST_SYNC_SRC.format(
            disable="  # graftlint: disable=semantic.host-sync",
            allow="", cap=8))
    assert rep2.findings == [] and not rep2.errors


def test_host_sync_allowlist_and_budget_pass(tmp_path, monkeypatch):
    # the same program is clean once the callback is allowlisted and
    # the declared fetch fits the byte budget
    rep = _run_fixture(tmp_path, monkeypatch, _HOST_SYNC_SRC.format(
        disable="", cap=512,
        allow=", allowed_callbacks=('debug_callback',)"))
    assert rep.findings == [] and not rep.errors


_COLLECTIVE_SRC = """
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from mmlspark_tpu.analysis.semantic import Case, hot_path_contract
from mmlspark_tpu.parallel.mesh import data_mesh
from jax import shard_map

@hot_path_contract({disable}
    "fix.collective", collective_budget={budget})
def contract():
    mesh = data_mesh()
    f = shard_map(lambda x: jax.lax.psum(x, "data"), mesh=mesh,
                  in_specs=P("data"), out_specs=P())
    return [Case("psum", f, (jnp.ones((8, 4), jnp.float32),))]
"""


def test_collective_budget_flags_and_suppresses(tmp_path, monkeypatch):
    # undeclared kind: the contract budgets nothing, the module has a
    # real all-reduce
    rep = _run_fixture(tmp_path, monkeypatch, _COLLECTIVE_SRC.format(
        budget="{}", disable=""))
    assert not rep.errors, rep.errors
    assert [f.rule for f in rep.findings] == [
        "semantic.collective-budget"], rep.findings
    assert "undeclared collective 'all-reduce'" in rep.findings[0].message
    # over budget: the kind is declared but the byte cap is too small
    rep2 = _run_fixture(tmp_path, monkeypatch, _COLLECTIVE_SRC.format(
        budget="{'all-reduce': {'ops': 4, 'bytes': 1}}", disable=""))
    assert [f.rule for f in rep2.findings] == [
        "semantic.collective-budget"], rep2.findings
    assert "over budget" in rep2.findings[0].message
    # within budget: clean
    rep3 = _run_fixture(tmp_path, monkeypatch, _COLLECTIVE_SRC.format(
        budget="{'all-reduce': {'ops': 8, 'bytes': 4096}}", disable=""))
    assert rep3.findings == [] and not rep3.errors
    # suppressed
    rep4 = _run_fixture(tmp_path, monkeypatch, _COLLECTIVE_SRC.format(
        budget="{}",
        disable="  # graftlint: disable=semantic.collective-budget"))
    assert rep4.findings == [] and not rep4.errors


_RECOMPILE_SRC = """
import jax.numpy as jnp
from mmlspark_tpu.analysis.semantic import Case, hot_path_contract

@hot_path_contract({disable}
    "fix.recompile", shape_buckets={{0: (0, (8, 16))}}{ok})
def contract():
    def f(x, scale):
        return x * scale
    return [Case("offbucket", f, (jnp.zeros((12, 4), jnp.float32), 0.5))]
"""


def test_recompile_hazard_flags_and_suppresses(tmp_path, monkeypatch):
    rep = _run_fixture(tmp_path, monkeypatch,
                       _RECOMPILE_SRC.format(ok="", disable=""))
    assert not rep.errors, rep.errors
    msgs = [f.message for f in rep.findings]
    assert all(f.rule == "semantic.recompile-hazard"
               for f in rep.findings), msgs
    assert any("python-scalar" in m for m in msgs), msgs
    assert any("not in the declared shape buckets" in m
               for m in msgs), msgs
    # weak_type_ok clears the scalar hazard, the bucket one stays
    rep2 = _run_fixture(tmp_path, monkeypatch, _RECOMPILE_SRC.format(
        ok=", weak_type_ok=(1,)", disable=""))
    msgs2 = [f.message for f in rep2.findings]
    assert len(msgs2) == 1 and "shape buckets" in msgs2[0], msgs2
    rep3 = _run_fixture(
        tmp_path, monkeypatch, _RECOMPILE_SRC.format(
            ok="", disable="  # graftlint: disable=semantic.recompile-hazard"))
    assert rep3.findings == [] and not rep3.errors


# ----------------------------------------- contract-import error paths
def test_missing_module_is_a_contract_import_error(tmp_path):
    rep = run_semantic(root=str(tmp_path),
                       entrypoints=[("_no_such_module_xyz", "contract")])
    assert len(rep.errors) == 1
    err = rep.errors[0]
    assert err.rule == "semantic.contract-import"
    assert "cannot import" in err.message
    assert err.line > 0 and err.path.endswith("registry.py")
    assert rep.findings == [] and rep.contracts == []


def test_missing_attr_and_wrong_type_are_import_errors(
        tmp_path, monkeypatch):
    name = f"_semfix_{next(_seq)}"
    (tmp_path / f"{name}.py").write_text("something = 42\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    try:
        rep = run_semantic(root=str(tmp_path),
                           entrypoints=[(name, "missing"),
                                        (name, "something")])
    finally:
        sys.modules.pop(name, None)
    msgs = sorted(f.message for f in rep.errors)
    assert len(msgs) == 2, msgs
    assert any("does not exist" in m for m in msgs), msgs
    assert any("not a HotPathContract" in m for m in msgs), msgs


_BROKEN_BUILDER_SRC = """
from mmlspark_tpu.analysis.semantic import Case, hot_path_contract

@hot_path_contract("fix.broken")
def contract():
    raise ValueError("cases exploded")
"""


def test_broken_case_builder_is_an_import_error(tmp_path, monkeypatch):
    rep = _run_fixture(tmp_path, monkeypatch, _BROKEN_BUILDER_SRC)
    assert len(rep.errors) == 1, rep.errors
    assert rep.errors[0].rule == "semantic.contract-import"
    assert "case builder raised ValueError" in rep.errors[0].message


# ------------------------------------------------------ CLI integration
def test_cli_all_tiers_exits_2_on_broken_registry(monkeypatch, capsys):
    from mmlspark_tpu.analysis import cli
    from mmlspark_tpu.analysis.semantic import registry
    monkeypatch.setattr(registry, "ENTRYPOINTS",
                        (("_no_such_module_xyz", "contract"),))
    rc = cli.main(["--root", _REPO, "--all-tiers",
                   "mmlspark_tpu/analysis/semantic/registry.py"])
    assert rc == 2, rc
    assert "semantic.contract-import" in capsys.readouterr().out


def test_cli_write_baseline_refuses_broken_registry(
        tmp_path, monkeypatch, capsys):
    # a broken contract registry must never be baselined away — and the
    # refusal must happen BEFORE any baseline file is written
    from mmlspark_tpu.analysis import cli
    from mmlspark_tpu.analysis.semantic import registry
    monkeypatch.setattr(registry, "ENTRYPOINTS",
                        (("_no_such_module_xyz", "contract"),))
    target = tmp_path / "b.json"
    rc = cli.main(["--root", _REPO, "--all-tiers", "--write-baseline",
                   "--baseline", str(target),
                   "mmlspark_tpu/analysis/semantic/registry.py"])
    assert rc == 2, rc
    assert not target.exists()
    assert "contract-import" in capsys.readouterr().err


def test_cli_select_semantic_rule_runs_only_that_checker(
        tmp_path, monkeypatch, capsys):
    from mmlspark_tpu.analysis import cli
    from mmlspark_tpu.analysis.semantic import registry
    name = f"_semfix_{next(_seq)}"
    (tmp_path / f"{name}.py").write_text(textwrap.dedent(
        _DONATION_SRC.format(disable="")))
    monkeypatch.syspath_prepend(str(tmp_path))
    monkeypatch.setattr(registry, "ENTRYPOINTS", ((name, "contract"),))
    try:
        # selecting a semantic id turns the tier on without --all-tiers;
        # no source ids selected -> the AST rules stay off
        rc = cli.main(["--root", str(tmp_path), "--strict",
                       "--select", "semantic.donation", f"{name}.py"])
    finally:
        sys.modules.pop(name, None)
    out = capsys.readouterr().out
    assert rc == 1, out
    assert "semantic.donation" in out
    # the seeded fixture ALSO violates executable-identity (grouped
    # cases with different shapes is fine here: expected_executables=2)
    # but unselected semantic rules must not report
    assert "semantic.executable-identity" not in out


def test_cli_select_unknown_semantic_rule_is_usage_error(capsys):
    from mmlspark_tpu.analysis import cli
    assert cli.main(["--root", _REPO, "--select", "semantic.nope"]) == 2
    assert "unknown rule" in capsys.readouterr().err


def test_cli_list_rules_groups_both_tiers(capsys):
    from mmlspark_tpu.analysis import cli
    assert cli.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "source tier" in out and "semantic tier" in out
    for rule in SEMANTIC_RULES:
        assert rule in out, rule


# ------------------------------------------------- baseline tier field
def test_baseline_tier_field_roundtrip(tmp_path):
    sem = Finding("semantic.donation", "mmlspark_tpu/io/plan.py", 10, 0,
                  "steady-state arg(s) [0] not donated", tier="semantic")
    src = Finding("wall-clock", "a.py", 1, 0, "time.time()")
    assert sem.to_dict()["tier"] == "semantic"
    assert src.to_dict()["tier"] == "source"
    b = Baseline.from_findings([sem, src])
    path = str(tmp_path / "b.json")
    b.save(path)
    with open(path) as f:
        data = json.load(f)
    # the format tag is unchanged — the tier map is additive, so v1
    # readers (and the committed empty baseline) keep working
    assert data["format"] == "graftlint-baseline-v1"
    assert data["tiers"] == {sem.key(): "semantic"}
    b2 = Baseline.load(path)
    assert b2.tiers == {sem.key(): "semantic"}
    b2.apply([sem, src])
    assert sem.baselined and src.baselined


def test_committed_baseline_still_loads_without_tiers():
    b = Baseline.load(os.path.join(_REPO, BASELINE_FILENAME))
    assert b.tiers == {}
