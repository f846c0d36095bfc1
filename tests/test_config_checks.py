"""X-layer self-tests: the lattice quotient itself, one violating fixture
per rule (delete-a-sweep-entry for X001, an inconsistent build gate and a
frozen-config bypass for X002, an unswept warm set and an illegal config
site for X003), the R/X partial --fix-baseline churn contract, and the
clean-tree run (the committed sweep fully covers the committed lattice)."""

import textwrap

from ddim_cold_tpu.analysis import config_checks as X
from ddim_cold_tpu.analysis import entries
from ddim_cold_tpu.analysis.findings import (
    RULES, Finding, load_baseline, rule_layer)


def _rules_of(findings):
    return sorted({f.rule for f in findings})


def _sweep_without(*labels):
    return [row for row in entries.serve_sweep() if row[0] not in labels]


# ------------------------------------------------------- lattice quotient


def test_lattice_enumerates_and_classes_quotient():
    lattice = X.enumerate_lattice()
    assert len(lattice) > 50  # a real product space, not a toy list
    classes = [cls for cls, _ in lattice]
    assert len(classes) == len(set(classes))
    # constants are invisible to the quotient: two k values, one class
    a = X.config_class(X.try_config(k=10))
    b = X.config_class(X.try_config(k=500))
    assert a == b
    # student is param routing, not a program class of its own
    assert X.config_class(X.try_config(steps=2)) == \
        X.config_class(X.try_config(steps=2, student=True))
    # but family/cache/seq axes DO split classes
    assert X.config_class(X.try_config(cache_interval=2)) != a
    assert X.config_class(X.try_config(preview_every=2)) != a
    assert X.config_class(X.try_config(task="inpaint"))[0] == "inpaint"
    assert X.config_class(X.try_config(steps=4))[0] == "fewstep"


# ------------------------------------------------------------------ X001


def test_x001_clean_on_committed_sweep():
    assert X.check_sweep_completeness() == []


def test_x001_deleting_the_cold_seq_witness_fires_once():
    # superres_l3_pv1 is the ONLY uncached cold sequence witness: deleting
    # it must produce exactly one finding, for exactly that class
    fs = X.check_sweep_completeness(_sweep_without("superres_l3_pv1"))
    assert len(fs) == 1
    f = fs[0]
    assert f.rule == "GRAFT-X001"
    assert f.subject == "class:cold/seq"
    assert f.path == "ddim_cold_tpu/analysis/entries.py"


def test_x001_deleting_the_full_mode_witness_fires_once():
    # the D2 axis: ddim_k500_ci2_full is the only cache_mode="full" entry
    fs = X.check_sweep_completeness(_sweep_without("ddim_k500_ci2_full"))
    assert len(fs) == 1
    assert fs[0].rule == "GRAFT-X001"
    assert fs[0].subject == "cache-mode:full"


def test_x001_deleting_a_redundant_entry_is_silent():
    # ddim_k500_tok2 exists as a J006 distinctness probe (token_k=2 vs 3
    # — structurally distinct gathers), not as lattice coverage: tok3
    # already witnesses the token class, so deleting tok2 fires nothing
    fs = X.check_sweep_completeness(_sweep_without("ddim_k500_tok2"))
    assert fs == []


def test_x001_quant_classification_is_pinned():
    from ddim_cold_tpu.serve.batching import _QUANT_MODES

    assert set(X.COVERED_QUANT) | set(X.EXCLUDED_QUANT) == set(_QUANT_MODES)


# ------------------------------------------------------------------ X002


def test_x002_clean_on_committed_gates():
    assert X.check_validation_consistency() == []


def test_x002_inconsistent_build_gate_fires():
    # a build gate that rejects "full" while construction accepts it:
    # exactly one disagreement in the probe grid
    def spec_fn(interval, mode, threshold, tokens):
        if mode == "full":
            return False
        return X._default_spec_fn(interval, mode, threshold, tokens)

    fs = X.check_validation_consistency(spec_fn=spec_fn)
    assert len(fs) == 1
    f = fs[0]
    assert f.rule == "GRAFT-X002"
    assert f.subject == "cache:ci2/full/th=None/tok=0"
    assert "construction accepts what build rejects" in f.message


def test_x002_frozen_config_bypass_lint():
    fs = X.lint_config_source(textwrap.dedent("""\
        def tweak(cfg):
            object.__setattr__(cfg, "quant", "xla")
            object.__setattr__(cfg, "not_a_field", 1)
            object.__setattr__(other, "quant", "xla")
    """), "fix.py")
    assert len(fs) == 1
    f = fs[0]
    assert f.rule == "GRAFT-X002"
    assert f.subject == "bypass:quant"
    assert f.line == 2


def test_x002_student_boundary():
    # the distill chain's step counts serve; the stride-student hole stays
    assert X.try_config(steps=1, student=True) is not None
    assert X.try_config(steps=4, student=True) is not None
    assert X.try_config(steps=0, student=True) is None


# ------------------------------------------------------------------ X003


def test_x003_clean_on_committed_warm_sets():
    assert X.check_warmup_soundness() == []


def test_x003_unswept_edit_class_fires_once():
    # drop the one witness of the cold uncached SEQUENCE class: the edit
    # warm set at preview_every=2 warms exactly that program unswept
    fs = X.check_warmup_soundness(sweep=_sweep_without("superres_l3_pv1"))
    assert len(fs) == 1
    f = fs[0]
    assert f.rule == "GRAFT-X003"
    assert f.subject == "edit-unswept:superres:pv2"


def test_x003_illegal_config_site_fires(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(textwrap.dedent("""\
        from ddim_cold_tpu.serve.batching import SamplerConfig

        GOOD = SamplerConfig(k=10, cache_interval=2)
        BAD = SamplerConfig(cache_mode="bogus")
        DYN = SamplerConfig(k=some_sweep_variable)
    """))
    fs = X.check_warmup_soundness(root=str(tmp_path))
    assert len(fs) == 1
    f = fs[0]
    assert f.rule == "GRAFT-X003"
    assert f.subject == "chip_smoke.py:4"
    assert f.line == 4


def test_x003_config_sites_substitute_variables():
    sites = X._config_sites(textwrap.dedent("""\
        a = SamplerConfig(k=K, cache_interval=2)
        b = SamplerConfig(steps=2)
        c = SamplerConfig(quant=mode_from_somewhere)
        d = SamplerConfig(**from_a_file)
    """))
    # a substitutes the representative for K; c's dynamic kwarg has no
    # representative and d is a splat, so both are skipped (no false alarm)
    assert [line for line, _ in sites] == [1, 2]
    assert sites[0][1] == {"k": 10, "cache_interval": 2}


# ------------------------------------------------- layer wiring + baseline


def test_x_rules_registered_and_layered():
    for rule in ("GRAFT-X001", "GRAFT-X002", "GRAFT-X003"):
        assert rule in RULES
        assert rule_layer(rule) == "config"


def test_clean_tree_config_layer():
    assert X.run_config_checks() == []


def test_cli_only_rx_partial_fix_baseline_churn(tmp_path, monkeypatch):
    """--fix-baseline --only R,X refreshes ONLY the protocol/config rule
    families; reviewed lines from the other seven layers ride along
    verbatim (the adoption path for the two new layers)."""
    from ddim_cold_tpu.analysis import cli

    base = str(tmp_path / "allow")
    ast_f = Finding("GRAFT-A002", "x.py", "f:except Exception", 1)
    r_f = Finding("GRAFT-R003", "ddim_cold_tpu/serve/remote.py",
                  "RemoteReplica.submit", 0)
    x_old = Finding("GRAFT-X001", "ddim_cold_tpu/analysis/entries.py",
                    "cache-mode:full", 0)
    x_new = Finding("GRAFT-X001", "ddim_cold_tpu/analysis/entries.py",
                    "class:cold/seq", 0)

    monkeypatch.setattr(cli, "collect", lambda *a, **k: [ast_f, r_f, x_old])
    assert cli.main(["--fix-baseline", base]) == 0
    assert load_baseline(base) == {ast_f.key, r_f.key, x_old.key}

    # an R,X-only rerun reports different R/X findings: the partial
    # refresh swaps those families and keeps the ast line untouched
    monkeypatch.setattr(cli, "collect", lambda *a, **k: [x_new])
    assert cli.main(["--only", "R,X", "--fix-baseline", base]) == 0
    assert load_baseline(base) == {ast_f.key, x_new.key}


def test_cli_only_x_runs_config_layer(capsys):
    from ddim_cold_tpu.analysis import cli

    assert cli.main(["--only", "X"]) == 0
    assert "[layers: config]" in capsys.readouterr().out
