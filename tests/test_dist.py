"""Distributed correctness on 8 host devices (subprocess-isolated).

Each case runs ``python -m repro.testing.dist_cases <case>`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` and asserts on the
JSON it prints: the BSP shuffle operators, MoE EP dispatch (== the
relational shuffle), flash-decode LSE merge, int8 pod-compressed training,
and elastic checkpoint restore across mesh shapes.
"""
import json
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_case(case: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-m", "repro.testing.dist_cases", case],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"{case} failed:\n{out.stdout}\n{out.stderr}"
    line = [l for l in out.stdout.splitlines() if l.startswith("JSON:")][-1]
    return json.loads(line[5:])


def test_dist_join_union_sort():
    r = run_case("join_union_sort")
    assert r["join_hash_rows"] == r["join_expect"], r
    assert r["join_sort_rows"] == r["join_expect"], r
    assert r["join_hash_overflow"] == 0
    assert r["union_rows"] == r["union_expect"], r
    assert r["sort_ok"], r


def test_dist_intersect_difference():
    r = run_case("intersect_difference")
    assert r["intersect_ok"] and r["difference_ok"], r


def test_dist_groupby_both_strategies():
    r = run_case("groupby")
    assert r["shuffle_ok"] and r["two_phase_ok"], r
    assert r["shuffle_overflow"] == 0 and r["two_phase_overflow"] == 0, r
    # the paper's two-phase claim: partial aggregates shuffle fewer rows
    assert r["two_phase_fewer_rows"], r


def test_plan_fused_matches_eager():
    """The tentpole contract: one fused shard_map program per chain, with
    strictly fewer AllToAlls and wire bytes, bit-identical to eager."""
    r = run_case("plan_fused")
    assert r["identical"], r
    assert r["eager_overflow"] == 0 and r["fused_overflow"] == 0, r
    assert r["fused_alltoall"] < r["eager_alltoall"], r
    assert r["fused_wire"] < r["eager_wire"], r


def test_sort_chain_elides_one_alltoall():
    """The range-provenance contract: fused sort->join runs exactly one
    fewer AllToAll than eager (the sorted side stays put, the other side
    range-aligns), with an identical row multiset; the surviving range tag
    then elides the downstream groupby shuffle entirely."""
    r = run_case("sort_chain")
    assert r["identical"], r
    assert r["eager_overflow"] == 0 and r["fused_overflow"] == 0, r
    assert r["fused_alltoall"] == r["eager_alltoall"] - 1, r
    assert r["groupby_elided"], r
    assert r["groupby_identical"], r


def test_sort_align_survives_probe_skew():
    """Default bucket sizing on the range-aligned join side must absorb a
    one-destination pileup (all probe keys in one anchor range) without
    overflow or divergence from eager."""
    r = run_case("sort_align_skew")
    assert r["identical"], r
    assert r["fused_overflow"] == 0, r


def test_global_limit_matches_local_oracle():
    """limit(n) is a true global head-n / post-sort top-n — bit-identical
    to the local oracle, never the per-shard heads."""
    r = run_case("global_limit")
    assert r["ok"], r
    assert r["limit_reported_zero"], r


def test_overflow_retry_recompiles_once_and_matches_oracle():
    """The cost model's safety contract: a skewed repartition whose
    stats-sized capacity overflows recompiles exactly once at conservative
    capacities and matches the local oracle bit-for-bit."""
    r = run_case("overflow_retry")
    assert r["retries"] == 1, r
    assert r["retries_after_repeat"] == 1, r  # repeat: straight to safe
    assert r["stats_dropped"], r  # bad estimates don't cascade downstream
    assert r["final_overflow"] == 0, r
    assert r["rows"] == r["rows_expect"], r
    assert r["identical"], r


def test_cost_model_groupby_strategy_and_wire():
    """Cost-driven physical planning: two_phase at low key cardinality,
    raw shuffle at high, strictly fewer dense wire bytes than the
    fixed-slack baseline at both ends, bit-identical results, no retry."""
    r = run_case("cost_groupby")
    assert r["retries"] == 0, r
    assert r["low"]["strategy"] == "two_phase", r
    assert r["high"]["strategy"] == "shuffle", r
    for end in ("low", "high"):
        assert r[end]["identical"], (end, r)
        assert r[end]["overflow"] == 0, (end, r)
        assert r[end]["cost_wire"] < r[end]["base_wire"], (end, r)


def test_window_chain_elides_shuffle_and_matches_oracle():
    """The window-subsystem contract: over a dist_sort output the window
    runs with 0 AllToAlls (boundary all_gather only) and is bit-identical
    to the single-host oracle for all 8 functions; the unsorted lowering
    (sort inside the window node) pays one shuffle and stays
    bit-identical too."""
    r = run_case("window_chain")
    assert r["identical"], r
    assert r["window_elided"], r
    assert r["fused_alltoall"] == 1, r  # only the sort's range partition
    assert r["naive_window_alltoall"] == 1, r
    assert r["fused_window_wire"] == 0, r
    assert r["naive_wire"] > 0, r
    assert r["naive_overflow"] == 0 and r["fused_overflow"] == 0, r
    assert r["rows"] == r["rows_expect"], r


def test_window_thin_shard_carries_match_oracle():
    """Group portions smaller than the lag/lead offset and an empty
    middle shard: the boundary buffers must merge across several shards
    and still match the single-host oracle bit-for-bit."""
    r = run_case("window_thin_shards")
    assert r["identical"], r
    assert r["window_elided"], r
    assert r["rows"] == r["rows_expect"], r


def test_dist_sort_multikey():
    r = run_case("sort_multikey")
    assert r["order_ok"] and r["multiset_ok"], r
    assert r["rows"] == r["rows_expect"], r
    assert r["overflow"] == 0, r


def test_dist_staged_shuffle():
    """The pipelined-shuffle contract on 8 devices: every staging and the
    ppermute ring are bit-identical to the monolithic exchange — same
    rows, same overflow under skew, same wire-byte accounting — and an
    empty (capacity-0) table shuffles without the old clip-bound crash."""
    r = run_case("staged_shuffle")
    assert r["overflow_positive"], r
    assert r["overflow_identical"] and r["rows_identical"], r
    assert r["staged_bitwise_equal"] and r["ring_bitwise_equal"], r
    assert r["wire_bytes_identical"], r
    assert r["stages_reported"] == [1, 3, 1], r
    assert r["modes_reported"] == ["alltoall", "alltoall", "ring"], r
    assert r["empty_rows"] == 0 and r["empty_overflow"] == 0, r


def test_verify_audit_matches_traced_collectives():
    """The collective auditor on 8 devices: verify.expected_collectives'
    static per-record accounting equals the collective counts in the
    actually-traced fused jaxpr, for every distributed operator family
    (hash groupby chain, sort->join alignment, sort->window carries,
    staged + ring repartitions, global limit)."""
    r = run_case("verify_audit")
    assert r["all_matched"], r
    # ring decomposes into ppermutes only; staging multiplies AllToAlls
    assert r["ring_shuffle"]["actual"]["all_to_all"] == 0, r
    assert r["ring_shuffle"]["actual"]["ppermute"] > 0, r
    assert (r["staged_shuffle"]["actual"]["all_to_all"]
            > r["groupby_chain"]["actual"]["all_to_all"]), r
    # range alignment and window boundary carries pay gathers, not A2As
    assert r["sort_join_align"]["actual"]["all_gather"] > 0, r
    assert r["sort_window"]["actual"]["all_gather"] > 0, r


def test_serving_async_interleaved_matches_sequential():
    """The serving contract: N interleaved collect_async clients over a
    shared session are bit-identical per query to sequential collects,
    the warm cache compiles NOTHING (inline keyless lambdas included),
    and resolving futures out of submission order changes nothing."""
    r = run_case("serving_async")
    assert r["identical"], r
    assert r["reverse_resolution_ok"], r
    assert r["cold_compiles"] > 0, r        # first pass really compiled
    assert r["warm_compiles"] == 0, r       # ... and never again
    assert r["warm_recompiles"] == 0, r
    assert r["async_qps"] > 0 and r["p99_ms"] > 0, r


def test_async_overflow_verification_is_deferred():
    """Deferred overflow verification: a wrong cost estimate is invisible
    at submit time (no host sync, future unresolved), discovered at
    result(), retried at safe capacities EXACTLY ONCE with oracle-exact
    rows; a repeat submit routes straight to the safe executable, and the
    sized + safe executables live under distinct cache namespaces."""
    r = run_case("async_overflow_deferred")
    assert r["deferred"], r
    assert r["retries"] == 1, r
    assert r["retries_after_repeat"] == 1, r
    assert r["idempotent"], r
    assert r["stats_dropped"], r
    assert r["rows"] == r["rows_expect"], r
    assert r["identical"], r
    assert "plan" in r["cache_namespaces"], r
    assert "plan-safe" in r["cache_namespaces"], r


def test_join_exchange_is_scoped_inside_the_join():
    r = run_case("exchange_scopes")
    for part in ("all_to_all", "pack_sort", "histogram"):
        assert r[part]["count"] >= 1, r
        assert r[part]["under_join_exchange"], r


def test_overflow_retry_emits_a_retry_span():
    r = run_case("overflow_retry_spans")
    assert r["retries"] == 1 and r["retry_spans"] == 1, r
    assert r["rung"] == "safe-capacity", r
    assert r["one_query"] and r["inside_verify"], r
    assert r["compile_namespaces"] == ["plan", "plan-safe"], r
    assert r["retry_compiles"] == 1, r


def test_moe_ep_matches_local():
    r = run_case("moe_ep")
    assert r["moe_ep_err"] < 2e-5, r
    assert r["aux_close"], r


def test_moe_decode_psum_matches_local():
    r = run_case("moe_decode_psum")
    assert r["moe_decode_err"] < 2e-5, r


def test_flash_decode_shard_matches_plain():
    r = run_case("flash_decode_shard")
    assert r["flash_decode_err"] < 2e-4, r


def test_pod_compressed_training_tracks_exact():
    r = run_case("compress_pod")
    # int8 quantization: per-step param drift stays small, loss matches
    assert r["pod_compress_max_param_diff"] < 5e-2, r
    assert r["loss_close"], r


def test_elastic_checkpoint_restore():
    r = run_case("elastic_restore")
    assert r["elastic_ok"], r
