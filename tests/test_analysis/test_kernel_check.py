"""Kernel contract checker (K1-K5) tests.

The seeded-mutation suite is the checker's own regression proof: each
known defect class (the dkv-GQA-pack bug family, VMEM busts, swapped
index maps, low-precision accumulators, unregistered env keys) must
trip EXACTLY its expected rule. The smoke audit runs a single-config
slice of the golden corpus so tier-1 stays fast; ``make kernel-audit``
sweeps the full corpus.
"""

import pytest

from magiattention_tpu.analysis.kernel_check import (
    _TOY_CONTRACTS,
    _TOY_FUSED_CONTRACTS,
    _TOY_FUSED_KERNEL_SRC,
    _TOY_KERNEL_SRC,
    _pallas_contracts,
    K5_ALLOWLIST,
    capture_decode_contracts,
    capture_ffa_contracts,
    check_contract,
    check_env_keys,
    check_kernel_sources,
    decode_corpus,
    discover_pallas_sites,
    golden_corpus,
    run_kernel_audit,
    run_seeded_mutations,
)
from magiattention_tpu.analysis.violation import VerifyReport


# -- discovery + annotation completeness ------------------------------------


def test_discovery_finds_every_pallas_site():
    sites = discover_pallas_sites()
    assert len(sites) == 18
    names = {s.kernel_name for s in sites}
    assert names == set(_pallas_contracts())
    assert {s.relpath for s in sites} == {
        "kernels/ffa.py", "kernels/paged_decode.py",
        "kernels/block_sparse.py", "kernels/ssd.py",
        "kernels/grouped_matmul.py",
    }


# -- source-level rules on the real kernels ---------------------------------


def test_real_kernel_sources_pass_k2_k4():
    report = VerifyReport()
    check_kernel_sources(report)
    assert report.fired_rules() == set()


def test_toy_kernel_source_is_clean():
    # the mutation base case: if this fires, the K2 mutation result is
    # meaningless
    report = VerifyReport()
    check_kernel_sources(report, _TOY_KERNEL_SRC, _TOY_CONTRACTS, "toy.py")
    assert report.fired_rules() == set()


def test_toy_fused_kernel_source_is_clean():
    # base case for the deleted_revisit_init mutation: the clean fused
    # toy (scratch accumulator + revisit-accumulated output) must satisfy
    # every K2 discipline rule including the qvf/qvl revisit rules
    report = VerifyReport()
    check_kernel_sources(
        report, _TOY_FUSED_KERNEL_SRC, _TOY_FUSED_CONTRACTS, "toy.py"
    )
    assert report.fired_rules() == set()


def test_revisit_overwrite_outside_guards_fires_k2():
    # a plain Assign to the revisit output outside the qvf/qvl blocks
    # would overwrite earlier work items' contributions on a revisit
    src = _TOY_FUSED_KERNEL_SRC.replace(
        "    dq_ref[0] += contrib", "    dq_ref[0] = contrib"
    )
    report = VerifyReport()
    check_kernel_sources(report, src, _TOY_FUSED_CONTRACTS, "toy.py")
    assert report.fired_rules() == {"K2"}
    assert any(
        "overwrite, not accumulate" in v.detail for v in report.violations
    )


@pytest.mark.parametrize("mutation,said", [
    # the copy-in of the aliased operand taken out: what the chip ran before
    # PR 30 (a later visit accumulates on whatever tile the window held)
    (("        dq_ref[0] = dqin_ref[0]", "        pass"), "read back"),
    # read back on every visit, the first too: the first-visit zero is lost
    (("    @pl.when(moved & (qvf == 0))", "    @pl.when(moved)"),
     "read back"),
])
def test_revisit_readback_is_required_by_k2(mutation, said):
    src = _TOY_FUSED_KERNEL_SRC.replace(*mutation)
    assert src != _TOY_FUSED_KERNEL_SRC
    report = VerifyReport()
    check_kernel_sources(report, src, _TOY_FUSED_CONTRACTS, "toy.py")
    assert report.fired_rules() == {"K2"}
    assert any(said in v.detail for v in report.violations)


# -- K5 on the real repo ----------------------------------------------------


def test_env_keys_clean_on_repo():
    report = VerifyReport()
    check_env_keys(report)
    assert report.fired_rules() == set()


def test_k5_allowlist_entries_carry_a_proof():
    for key, why in K5_ALLOWLIST.items():
        assert key.startswith("MAGI_ATTENTION_")
        assert len(why) > 20  # a proof sketch, not a shrug


# -- seeded mutations (ISSUE acceptance: exactly the expected rule) ---------


def test_seeded_mutations_fire_exactly_their_rule():
    results = run_seeded_mutations()
    assert len(results) == 11
    assert {r["expected_rule"] for r in results} == {
        "K1", "K2", "K3", "K4", "K5"
    }
    assert {r["mutation"] for r in results} >= {
        "corrupted_extent_row", "deleted_revisit_init",
        "deleted_revisit_readback", "oob_page_table", "oob_block_table",
        "misrouted_scale_prefetch",
    }
    for r in results:
        assert r["ok"], (
            f"mutation {r['mutation']} expected {{'{r['expected_rule']}'}} "
            f"but fired {r['fired_rules']}"
        )


# -- audit smoke (single-config slice; full corpus is `make kernel-audit`) --


@pytest.fixture(scope="module")
def smoke_audit():
    corpus = [
        s for s in golden_corpus()
        if s.name == "causal/bfloat16/g4/b128x128"
    ]
    assert corpus, "golden corpus no longer contains the smoke config"
    return run_kernel_audit(corpus=corpus)


def test_smoke_audit_is_clean(smoke_audit):
    report, _ = smoke_audit
    assert not report.violations, "\n".join(
        str(v) for v in report.violations
    )


def test_smoke_audit_covers_all_kernels_and_reports_vmem(smoke_audit):
    # one g=4 config exercises all six kernels (unpacked + GQA-packed per
    # pass), which is exactly why it is the smoke slice
    report, rows = smoke_audit
    config_rows = [r for r in rows if r["config"] != "reachable_space_sweep"]
    assert {r["kernel"] for r in config_rows} == set(_pallas_contracts())
    for r in config_rows:
        assert 0 < r["vmem_bytes"] <= r["vmem_total_bytes"]
        assert r["vmem_total_bytes"] <= r["vmem_allowed_bytes"]
    sweep = [r for r in rows if r["config"] == "reachable_space_sweep"]
    assert len(sweep) == 1 and sweep[0]["configs_checked"] > 0
    assert sweep[0]["worst_bytes"] <= sweep[0]["allowed_bytes"]


def test_decode_corpus_contracts_are_clean():
    # the paged-decode kernel family joins the audit corpus: every config
    # must capture exactly one contract (of its variant's kernel) and pass
    # K1/K3/K4 on it
    expected = {
        "base": "_paged_decode_kernel",
        "spec": "_paged_decode_spec_kernel",
        "int8": "_paged_decode_int8_kernel",
    }
    seen = set()
    for dspec in decode_corpus():
        contracts = capture_decode_contracts(dspec)
        assert [c.kernel_name for c in contracts] == [expected[dspec.variant]]
        seen.add(dspec.variant)
        report = VerifyReport()
        check_contract(report, contracts[0], dspec.name)
        assert report.fired_rules() == set(), "\n".join(
            str(v) for v in report.violations
        )
    assert seen == set(expected)


def test_check_contract_is_deterministic(smoke_audit):
    # captured contracts are pure data: re-checking one must not
    # accumulate state or flake
    corpus = [
        s for s in golden_corpus()
        if s.name == "causal/bfloat16/g4/b128x128"
    ]
    contracts = capture_ffa_contracts(corpus[0])
    for contract in contracts:
        for _ in range(2):
            report = VerifyReport()
            check_contract(report, contract)
            assert report.fired_rules() == set()
