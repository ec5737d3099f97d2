"""Aggregate and print a run summary from telemetry JSONL files.

Reads the ``magiattention-<pid>.jsonl`` files a run produced under
MAGI_ATTENTION_TELEMETRY_DIR (one record per dispatch solve / plan build /
attention step, schema in docs/observability.md) and prints a human
summary: dispatch balance, per-stage comm volumes (payload vs wire vs
alignment-padding waste), kernel-plan padding efficiency, step timings,
and runtime-cache behavior.

Usage::

    MAGI_ATTENTION_TELEMETRY=1 MAGI_ATTENTION_TELEMETRY_DIR=/tmp/tel \
        python my_run.py
    python scripts/telemetry_report.py /tmp/tel          # a directory
    python scripts/telemetry_report.py /tmp/tel/*.jsonl  # or files
    python scripts/telemetry_report.py --json /tmp/tel   # machine-readable
    python scripts/telemetry_report.py --store /tmp/tel/store --json /tmp/tel
    python scripts/telemetry_report.py --schema            # --json field docs
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

SUPPORTED_SCHEMA = 1

# Field documentation for every --json section (printed by --schema).
# Top-level keys of the --json object == keys here; each maps field name ->
# one-line meaning. Sections are omitted from the output when no record of
# the backing kind was seen.
SECTION_SCHEMAS: dict[str, dict[str, str]] = {
    "record_counts": {
        "<kind>": "number of records of each telemetry kind seen",
    },
    "dispatch": {
        "solves": "dispatch_meta records (solver runs)",
        "alg": "balance algorithm of the last solve",
        "cp_size": "context-parallel world size",
        "num_chunks": "chunks balanced per rank",
        "per_rank_area": "attention area per rank after balancing",
        "max_area": "largest per-rank area",
        "lower_bound": "area lower bound (perfect balance)",
        "balance_ratio": "max_area / lower_bound (1.0 = perfect)",
    },
    "comm_plan": {
        "builds": "plan_build records",
        "planner": "planner of the last build (static/dynamic)",
        "stages": "per-stage lowering + payload/wire/padding rows",
    },
    "attn_step": {
        "steps": "attn_step records",
        "backend": "kernel backend of the last step",
        "overlap_degree": "comm/compute overlap stages",
        "block_q": "FFA q tile rows",
        "block_k": "FFA k tile cols",
        "payload_bytes_total": "useful comm bytes, last step",
        "wire_bytes_total": "on-wire comm bytes, last step",
        "padding_bytes_total": "alignment-padding waste, last step",
        "band_elems": "true mask-band elements",
        "padded_elems": "padded kernel-grid elements",
        "est_flops_fwd": "forward FLOPs over the true band",
        "padded_flops_fwd": "forward FLOPs over the padded grid",
        "stages": "per-stage comm detail of the last step",
        "wall_ms_last": "host wall of the last step (ms)",
        "wall_ms_min": "fastest step wall (ms; post-compile)",
        "bwd_mode": "backward mode of the last step (fused/split)",
        "bwd_modes": "step counts per backward mode",
    },
    "ffa_plans": {
        "plans": "ffa_plan records",
        "padded_elems": "padded grid elements, all plans",
        "band_elems": "true band elements, all plans",
        "executed_elems": "extent-clamped executed elements",
        "padding_ratio": "padded / band",
        "executed_ratio": "executed / band",
        "extent_clamp": "extent clamping active on the last plan",
        "frag_histogram": "slice counts bucketed by tile-cover ratio",
    },
    "mixed_dispatch": {
        "splits": "mixed_dispatch records (accepted splits)",
        "forced": "splits forced by pin rather than profitability",
        "num_dense": "slices routed to the coarse tiling, last split",
        "num_frag": "slices routed to the fine tiling, last split",
        "coarse_blocks": "coarse (bq, bk)",
        "fine_blocks": "fine (bq, bk)",
        "single_score": "modeled cost of the single-tiling plan",
        "split_score": "modeled cost of the mixed plan",
    },
    "tile_policy": {
        "picks": "tile_policy records",
        "mode": "selection mode of the last pick",
        "fwd_blocks": "forward (bq, bk)",
        "dq_blocks": "dq-pass blocks (null = inherit fwd)",
        "dkv_blocks": "dkv-pass blocks (null = inherit fwd)",
        "candidates_scored": "tilings scored by the cost model",
    },
    "runtime_cache": {
        "hits": "runtime LRU hits",
        "misses": "runtime LRU misses",
        "evictions": "runtime LRU evictions",
        "size": "current entries",
        "maxsize": "capacity",
    },
    "plan_verify": {
        "runs": "plan_verify records",
        "planner": "planner verified last",
        "rules_run": "verifier rules executed",
        "errors_total": "errors across runs",
        "warnings_total": "warnings across runs",
        "fired_rules": "rules that fired at least once",
        "wall_ms_last": "last verify wall (ms)",
        "wall_ms_total": "total verify wall (ms)",
    },
    "kernel_audit": {
        "runs": "kernel_audit records",
        "kernels": "kernels audited",
        "configs": "configs per kernel",
        "rules_run": "audit rules executed",
        "errors_total": "errors across runs",
        "warnings_total": "warnings across runs",
        "fired_rules": "rules that fired at least once",
        "vmem_worst_bytes": "worst-case modeled VMEM residency",
        "vmem_worst_config": "config hitting the worst case",
        "vmem_allowed_bytes": "modeled VMEM budget",
    },
    "resilience": {
        "events": "resilience records",
        "injected": "faults injected",
        "guard_trips": "numeric guard trips",
        "fallback_hops": "fallback ladder hops",
        "retries": "bounded retries",
        "recovered": "successful recoveries",
        "hops_by_site": "fallback/retry counts per site",
    },
    "serve": {
        "steps": "serve_step records",
        "admitted_total": "requests admitted",
        "evicted_total": "requests evicted",
        "completed_total": "requests completed",
        "prefill_tokens_total": "prefill tokens processed",
        "decode_tokens_total": "decode tokens produced",
        "occupancy_mean": "mean slot occupancy",
        "pages_in_use_last": "KV pages in use after the last step",
        "pages_in_use_max": "peak KV pages in use",
        "wall_ms_mean": "mean step wall (ms)",
        "wall_ms_max": "max step wall (ms)",
        "kv_dtype": "KV cache dtype, last step",
        "shards": "decode kv-head mesh width, last step",
        "spec_k": "draft tokens verified per tick, last step",
        "draft_attempted_total": "speculative draft rows attempted",
        "draft_accepted_total": "speculative draft rows committed",
        "accept_rate": "accepted / attempted draft rows",
        "accepted_per_tick": "committed tokens per decoding tick",
    },
    "nsa": {
        "steps": "nsa_step records",
        "slc_backend": "slc-branch backend of the last step",
        "backends": "step counts per slc backend",
        "top_k": "selected blocks per (kv-head, q-block), last step",
        "hk": "kv heads, last step",
        "n_qb": "query blocks, last step",
        "l_slc": "selection block length, last step",
        "d_stride": "block stride, last step",
        "executed_bytes_total": "modeled HBM KV bytes streamed, all steps",
        "gathered_bytes_total": "modeled bytes a gathered slc would move",
        "gather_savings_ratio": "gathered / executed (>1 = gather-free wins)",
    },
    "plan_solve": {
        "events": "plan_solve records",
        "solves": "actual solver runs",
        "cache_hits": "plan-cache hits",
        "cold": "from-scratch solves",
        "incremental": "incremental re-solves",
        "planners": "record counts per planner",
        "rows_total": "chunk rows seen by solves",
        "rows_resolved": "chunk rows actually re-solved",
        "resolve_fraction": "rows_resolved / rows_total",
        "incremental_resolve_fraction": "same, incremental solves only",
        "wall_ms_total": "total solver wall (ms)",
        "wall_ms_mean": "mean solver wall (ms)",
        "two_level_solves": "solves priced with the (dcn, ici) model",
    },
    "plan_control_plane": {
        "resolutions": "plan_solve records carrying a source tag",
        "by_source": "resolutions per tier (cold/memory/disk/broadcast)",
        "store_reads": "plan_store read records",
        "store_hits": "store reads that decoded + verified clean",
        "store_misses": "store reads degraded to a typed miss",
        "store_miss_reasons": "miss counts per reason",
        "store_writes": "atomic store publishes that landed",
        "store_orphans_removed": "crash-orphan .tmp files collected",
        "broadcasts": "plan_broadcast exchange records",
        "broadcast_by_role": "exchanges per role (leader/follower)",
        "broadcast_exhausted": "exchanges that burned every retry",
        "broadcast_attempts_total": "receive attempts across exchanges",
        "broadcast_backoff_ms_total": "total backoff slept (ms)",
    },
    "hier_comm": {
        "plans": "hier_plan records",
        "dcn_rows": "DCN rows after dedup, last plan",
        "flat_dcn_rows": "DCN rows a flat plan would move",
        "dcn_dedup_ratio": "flat / dedup DCN rows",
    },
    "backend_select": {
        "selections": "backend_select records (one per decision+key+choice)",
        "by_decision": "per decision: choice counts, source counts, last",
        "sources": "total counts per resolution source "
                   "(pin/heuristic, or a call site's own rule)",
    },
    "rank_health": {
        "observations": "rank_health records (one per observed step wall)",
        "ranks": "distinct ranks observed",
        "degraded_now": "ranks whose last record shows capacity < 1",
        "transitions": "degraded/recovered transition counts",
        "per_rank": "per rank: last ewma_ms, capacity, degraded flag",
        "capacities_last": "capacity vector from each rank's last record",
    },
    "step_retry": {
        "events": "step_retry records (one per failed watchdog attempt)",
        "quarantines": "retries whose trip quarantined the backend",
        "by_from_backend": "failed attempts per originating backend",
        "by_error": "failed attempts per error type",
        "last": "the most recent retry (stage, attempt, from, to, error)",
    },
    "store": {
        "dir": "store directory read (--store)",
        "history": "run-history aggregate counts per kind",
        "rank_health_rows": "persisted per-rank health aggregates",
        "quarantine_rows": "persisted quarantined (decision, key, backend)",
    },
}


def load_records(paths: list[str]) -> list[dict]:
    """Parse records from JSONL files and/or directories of them.

    Skips unparseable lines (a crashed run can truncate its last record)
    and records from a newer schema than this reader understands.
    """
    files: list[str] = []
    for p in paths:
        if os.path.isdir(p):
            files.extend(sorted(glob.glob(os.path.join(p, "*.jsonl"))))
        else:
            files.append(p)
    records: list[dict] = []
    for fp in files:
        with open(fp) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if rec.get("schema_version", 0) > SUPPORTED_SCHEMA:
                    continue
                records.append(rec)
    records.sort(key=lambda r: (r.get("ts", 0), r.get("seq", 0)))
    return records


def _by_kind(records: list[dict]) -> dict[str, list[dict]]:
    out: dict[str, list[dict]] = {}
    for r in records:
        out.setdefault(r.get("kind", "?"), []).append(r)
    return out


def aggregate(records: list[dict]) -> dict:
    """Cross-record aggregates keyed by section (the printed summary's
    data; also the --json output)."""
    kinds = _by_kind(records)
    agg: dict = {"record_counts": {k: len(v) for k, v in sorted(kinds.items())}}

    metas = kinds.get("dispatch_meta", [])
    if metas:
        last = metas[-1]
        agg["dispatch"] = {
            "solves": len(metas),
            "alg": last.get("alg"),
            "cp_size": last.get("cp_size"),
            "num_chunks": last.get("num_chunks"),
            "per_rank_area": last.get("per_rank_area"),
            "max_area": last.get("max_area"),
            "lower_bound": last.get("lower_bound"),
            "balance_ratio": last.get("balance_ratio"),
        }

    plans = kinds.get("plan_build", [])
    if plans:
        last = plans[-1]
        stages = []
        for s in last.get("stages", []):
            stages.append({
                "stage": s.get("stage", s.get("name")),
                "lowering": s.get("lowering_executed",
                                  s.get("lowering_planned")),
                "payload_rows": s.get("payload_rows"),
                "wire_rows": s.get("wire_rows"),
                "padding_rows": s.get("padding_rows"),
                "wire_ratio": s.get("wire_ratio"),
            })
        agg["comm_plan"] = {
            "builds": len(plans),
            "planner": last.get("planner"),
            "stages": stages,
        }

    steps = kinds.get("attn_step", [])
    if steps:
        last = steps[-1]
        walls = [s["wall_ms"] for s in steps if s.get("wall_ms") is not None]
        agg["attn_step"] = {
            "steps": len(steps),
            "backend": last.get("backend"),
            "overlap_degree": last.get("overlap_degree"),
            "block_q": last.get("block_q"),
            "block_k": last.get("block_k"),
            "payload_bytes_total": last.get("payload_bytes_total"),
            "wire_bytes_total": last.get("wire_bytes_total"),
            "padding_bytes_total": last.get("padding_bytes_total"),
            "band_elems": last.get("band_elems"),
            "padded_elems": last.get("padded_elems"),
            "est_flops_fwd": last.get("est_flops_fwd"),
            "padded_flops_fwd": last.get("padded_flops_fwd"),
            "stages": last.get("stages"),
            "wall_ms_last": walls[-1] if walls else None,
            "wall_ms_min": min(walls) if walls else None,
        }
        # fused-vs-split backward: which mode the dispatch resolved per
        # step (stamped from resolved_bwd_mode; absent on sdpa backends)
        modes: dict[str, int] = {}
        for s in steps:
            m = s.get("bwd_mode")
            if m:
                modes[m] = modes.get(m, 0) + 1
        if modes:
            agg["attn_step"]["bwd_mode"] = last.get("bwd_mode")
            agg["attn_step"]["bwd_modes"] = dict(sorted(modes.items()))

    ffa = kinds.get("ffa_plan", [])
    if ffa:
        padded = sum(r.get("padded_elems", 0) for r in ffa)
        band = sum(r.get("band_elems", 0) for r in ffa)
        executed = sum(r.get("executed_elems", 0) for r in ffa)
        frag_hist: dict[str, int] = {}
        for r in ffa:
            for bucket, n in (r.get("frag_histogram") or {}).items():
                frag_hist[bucket] = frag_hist.get(bucket, 0) + n
        agg["ffa_plans"] = {
            "plans": len(ffa),
            "padded_elems": padded,
            "band_elems": band,
            "executed_elems": executed,
            "padding_ratio": padded / band if band else None,
            "executed_ratio": executed / band if band else None,
            "extent_clamp": ffa[-1].get("extent_clamp"),
            "frag_histogram": frag_hist or None,
        }

    mixed = kinds.get("mixed_dispatch", [])
    if mixed:
        last = mixed[-1]
        agg["mixed_dispatch"] = {
            "splits": len(mixed),
            "forced": sum(1 for r in mixed if r.get("forced")),
            "num_dense": last.get("num_dense"),
            "num_frag": last.get("num_frag"),
            "coarse_blocks": last.get("coarse_blocks"),
            "fine_blocks": last.get("fine_blocks"),
            "single_score": last.get("single_score"),
            "split_score": last.get("split_score"),
        }

    tiles = kinds.get("tile_policy", [])
    if tiles:
        last = tiles[-1]
        agg["tile_policy"] = {
            "picks": len(tiles),
            "mode": last.get("mode"),
            "fwd_blocks": last.get("fwd_blocks"),
            "dq_blocks": last.get("dq_blocks"),
            "dkv_blocks": last.get("dkv_blocks"),
            "candidates_scored": last.get("candidates_scored"),
        }

    caches = kinds.get("runtime_cache", [])
    if caches:
        agg["runtime_cache"] = {
            k: caches[-1].get(k)
            for k in ("hits", "misses", "evictions", "size", "maxsize")
        }

    verifies = kinds.get("plan_verify", [])
    if verifies:
        last = verifies[-1]
        walls = [
            v["wall_ms"] for v in verifies if v.get("wall_ms") is not None
        ]
        agg["plan_verify"] = {
            "runs": len(verifies),
            "planner": last.get("planner"),
            "rules_run": last.get("rules_run"),
            "errors_total": sum(v.get("errors", 0) for v in verifies),
            "warnings_total": sum(v.get("warnings", 0) for v in verifies),
            "fired_rules": sorted(
                {r for v in verifies for r in v.get("fired_rules", [])}
            ),
            "wall_ms_last": walls[-1] if walls else None,
            "wall_ms_total": sum(walls) if walls else None,
        }

    audits = kinds.get("kernel_audit", [])
    if audits:
        last = audits[-1]
        agg["kernel_audit"] = {
            "runs": len(audits),
            "kernels": last.get("kernels"),
            "configs": last.get("configs"),
            "rules_run": last.get("rules_run"),
            "errors_total": sum(a.get("errors", 0) for a in audits),
            "warnings_total": sum(a.get("warnings", 0) for a in audits),
            "fired_rules": sorted(
                {r for a in audits for r in a.get("fired_rules", [])}
            ),
            "vmem_worst_bytes": last.get("vmem_worst_bytes"),
            "vmem_worst_config": last.get("vmem_worst_config"),
            "vmem_allowed_bytes": last.get("vmem_allowed_bytes"),
        }

    res = kinds.get("resilience", [])
    if res:
        by_action: dict[str, int] = {}
        hops_by_site: dict[str, int] = {}
        for r in res:
            action = r.get("action", "?")
            by_action[action] = by_action.get(action, 0) + 1
            if action in ("fallback", "retry"):
                site = r.get("site", "?")
                hops_by_site[site] = hops_by_site.get(site, 0) + 1
        agg["resilience"] = {
            "events": len(res),
            "injected": by_action.get("inject", 0),
            "guard_trips": by_action.get("guard_trip", 0),
            "fallback_hops": by_action.get("fallback", 0),
            "retries": by_action.get("retry", 0),
            "recovered": by_action.get("recovered", 0),
            "hops_by_site": dict(sorted(hops_by_site.items())),
        }

    serves = kinds.get("serve_step", [])
    if serves:
        walls = [s["wall_ms"] for s in serves if s.get("wall_ms") is not None]
        occ = [
            s["occupancy"] for s in serves if s.get("occupancy") is not None
        ]
        pages = [
            s["pages_in_use"] for s in serves
            if s.get("pages_in_use") is not None
        ]
        agg["serve"] = {
            "steps": len(serves),
            "admitted_total": sum(s.get("admitted", 0) for s in serves),
            "evicted_total": sum(s.get("evicted", 0) for s in serves),
            "completed_total": sum(s.get("completed", 0) for s in serves),
            "prefill_tokens_total": sum(
                s.get("prefill_tokens", 0) for s in serves
            ),
            "decode_tokens_total": sum(
                s.get("decode_tokens", 0) for s in serves
            ),
            "occupancy_mean": sum(occ) / len(occ) if occ else None,
            "pages_in_use_last": pages[-1] if pages else None,
            "pages_in_use_max": max(pages) if pages else None,
            "wall_ms_mean": sum(walls) / len(walls) if walls else None,
            "wall_ms_max": max(walls) if walls else None,
        }
        # serving-scale stamps (kv_dtype / shards / spec_k are config-
        # static per engine, so 'last' == the run's setting; accept stats
        # aggregate over every tick that decoded)
        attempted = sum(s.get("draft_attempted", 0) for s in serves)
        accepted = sum(s.get("draft_accepted", 0) for s in serves)
        ticks = sum(1 for s in serves if s.get("draft_attempted", 0))
        agg["serve"].update({
            "kv_dtype": serves[-1].get("kv_dtype"),
            "shards": serves[-1].get("shards"),
            "spec_k": serves[-1].get("spec_k"),
            "draft_attempted_total": attempted,
            "draft_accepted_total": accepted,
            "accept_rate": accepted / attempted if attempted else None,
            "accepted_per_tick": accepted / ticks if ticks else None,
        })

    nsa = kinds.get("nsa_step", [])
    if nsa:
        last = nsa[-1]
        backends: dict[str, int] = {}
        for r in nsa:
            b = r.get("slc_backend", "?")
            backends[b] = backends.get(b, 0) + 1
        executed = sum(r.get("executed_bytes", 0) for r in nsa)
        gathered = sum(r.get("gathered_bytes", 0) for r in nsa)
        agg["nsa"] = {
            "steps": len(nsa),
            "slc_backend": last.get("slc_backend"),
            "backends": dict(sorted(backends.items())),
            "top_k": last.get("top_k"),
            "hk": last.get("hk"),
            "n_qb": last.get("n_qb"),
            "l_slc": last.get("l_slc"),
            "d_stride": last.get("d_stride"),
            "executed_bytes_total": executed,
            "gathered_bytes_total": gathered,
            "gather_savings_ratio": (
                gathered / executed if executed else None
            ),
        }

    solves = kinds.get("plan_solve", [])
    if solves:
        solved = [r for r in solves if r.get("event") == "solve"]
        hits = [r for r in solves if r.get("event") == "cache_hit"]
        incr = [r for r in solved if r.get("incremental")]
        walls = [r["wall_ms"] for r in solved if r.get("wall_ms") is not None]
        rows_total = sum(r.get("rows_total", 0) for r in solved)
        rows_resolved = sum(r.get("rows_resolved", 0) for r in solved)
        inc_total = sum(r.get("rows_total", 0) for r in incr)
        inc_resolved = sum(r.get("rows_resolved", 0) for r in incr)
        planners: dict[str, int] = {}
        for r in solves:
            p = r.get("planner", "?")
            planners[p] = planners.get(p, 0) + 1
        agg["plan_solve"] = {
            "events": len(solves),
            "solves": len(solved),
            "cache_hits": len(hits),
            "cold": len(solved) - len(incr),
            "incremental": len(incr),
            "planners": dict(sorted(planners.items())),
            "rows_total": rows_total,
            "rows_resolved": rows_resolved,
            "resolve_fraction": (
                rows_resolved / rows_total if rows_total else None
            ),
            "incremental_resolve_fraction": (
                inc_resolved / inc_total if inc_total else None
            ),
            "wall_ms_total": sum(walls) if walls else None,
            "wall_ms_mean": sum(walls) / len(walls) if walls else None,
            "two_level_solves": sum(
                1 for r in solved if r.get("two_level")
            ),
        }

    stores = kinds.get("plan_store", [])
    bcasts = kinds.get("plan_broadcast", [])
    sourced = [r for r in kinds.get("plan_solve", []) if r.get("source")]
    if stores or bcasts or sourced:
        by_source: dict[str, int] = {}
        for r in sourced:
            by_source[r["source"]] = by_source.get(r["source"], 0) + 1
        reads = [r for r in stores if r.get("op") == "read"]
        writes = [r for r in stores if r.get("op") == "write"]
        cleanups = [r for r in stores if r.get("op") == "cleanup"]
        reasons: dict[str, int] = {}
        for r in reads:
            if r.get("outcome") == "miss":
                reason = r.get("reason", "?")
                reasons[reason] = reasons.get(reason, 0) + 1
        by_role: dict[str, int] = {}
        for r in bcasts:
            role = r.get("role", "?")
            by_role[role] = by_role.get(role, 0) + 1
        agg["plan_control_plane"] = {
            "resolutions": len(sourced),
            "by_source": dict(sorted(by_source.items())),
            "store_reads": len(reads),
            "store_hits": sum(
                1 for r in reads if r.get("outcome") == "hit"
            ),
            "store_misses": sum(
                1 for r in reads if r.get("outcome") == "miss"
            ),
            "store_miss_reasons": dict(sorted(reasons.items())),
            "store_writes": sum(
                1 for r in writes if r.get("outcome") == "ok"
            ),
            "store_orphans_removed": sum(
                r.get("removed", 0) for r in cleanups
            ),
            "broadcasts": len(bcasts),
            "broadcast_by_role": dict(sorted(by_role.items())),
            "broadcast_exhausted": sum(
                1 for r in bcasts if r.get("outcome") == "exhausted"
            ),
            "broadcast_attempts_total": sum(
                r.get("attempts", 1) for r in bcasts
            ),
            "broadcast_backoff_ms_total": sum(
                r.get("backoff_ms", 0.0) for r in bcasts
            ),
        }

    hier = kinds.get("hier_plan", [])
    if hier:
        last = hier[-1]
        agg["hier_comm"] = {
            "plans": len(hier),
            "dcn_rows": last.get("dcn_rows"),
            "flat_dcn_rows": last.get("flat_dcn_rows"),
            "dcn_dedup_ratio": last.get("dcn_dedup_ratio"),
        }

    selects = kinds.get("backend_select", [])
    if selects:
        by_decision: dict[str, dict] = {}
        sources: dict[str, int] = {}
        for r in selects:
            dec = r.get("decision", "?")
            d = by_decision.setdefault(
                dec, {"choices": {}, "sources": {}, "last_choice": None}
            )
            choice = r.get("choice", "?")
            src = r.get("source", "?")
            d["choices"][choice] = d["choices"].get(choice, 0) + 1
            d["sources"][src] = d["sources"].get(src, 0) + 1
            d["last_choice"] = choice
            sources[src] = sources.get(src, 0) + 1
        agg["backend_select"] = {
            "selections": len(selects),
            "by_decision": {
                k: by_decision[k] for k in sorted(by_decision)
            },
            "sources": dict(sorted(sources.items())),
        }

    health = kinds.get("rank_health", [])
    if health:
        per_rank: dict[int, dict] = {}
        transitions: dict[str, int] = {}
        for r in health:
            rank = r.get("rank")
            if rank is None:
                continue
            per_rank[int(rank)] = {
                "ewma_ms": r.get("ewma_ms"),
                "capacity": r.get("capacity"),
                "degraded": r.get("degraded"),
            }
            t = r.get("transition")
            if t:
                transitions[t] = transitions.get(t, 0) + 1
        ranks = sorted(per_rank)
        agg["rank_health"] = {
            "observations": len(health),
            "ranks": len(per_rank),
            "degraded_now": sum(
                1 for d in per_rank.values() if d.get("degraded")
            ),
            "transitions": dict(sorted(transitions.items())),
            "per_rank": {str(r): per_rank[r] for r in ranks},
            "capacities_last": [per_rank[r].get("capacity") for r in ranks],
        }

    retries = kinds.get("step_retry", [])
    if retries:
        by_from: dict[str, int] = {}
        by_error: dict[str, int] = {}
        for r in retries:
            fb = str(r.get("from_backend", "?"))
            by_from[fb] = by_from.get(fb, 0) + 1
            err = str(r.get("error", "?"))
            by_error[err] = by_error.get(err, 0) + 1
        last = retries[-1]
        agg["step_retry"] = {
            "events": len(retries),
            "quarantines": sum(1 for r in retries if r.get("quarantined")),
            "by_from_backend": dict(sorted(by_from.items())),
            "by_error": dict(sorted(by_error.items())),
            "last": {
                k: last.get(k)
                for k in (
                    "stage", "attempt", "from_backend", "to_backend",
                    "error",
                )
            },
        }
    return agg


def aggregate_store(store_dir: str) -> dict:
    """The persistent store's aggregate view (--store): reads
    ``store.json`` + ``history-*.jsonl`` via the package's own loader."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from magiattention_tpu.telemetry.store import _load_from_disk

    state = _load_from_disk(store_dir)
    history: dict[str, int] = {}
    for h in state.history.values():
        kind = h.get("kind", "?")
        history[kind] = history.get(kind, 0) + 1
    return {
        "dir": store_dir,
        "history": dict(sorted(history.items())),
        "rank_health_rows": len(state.rank_health),
        "quarantine_rows": len(state.quarantine),
    }


def _fmt_bytes(n) -> str:
    if n is None:
        return "?"
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{n} B"
        n /= 1024
    return f"{n}"


def format_summary(agg: dict) -> str:
    lines = ["# magiattention telemetry summary"]
    counts = agg.get("record_counts", {})
    lines.append(
        "records: "
        + (", ".join(f"{k}={v}" for k, v in counts.items()) or "none")
    )

    d = agg.get("dispatch")
    if d:
        lines.append("")
        lines.append(
            f"dispatch [{d['alg']}] cp={d['cp_size']} "
            f"chunks={d['num_chunks']} solves={d['solves']}"
        )
        lines.append(
            f"  balance_ratio={d['balance_ratio']:.4f} "
            f"(max_area={d['max_area']} lower_bound={d['lower_bound']})"
        )
        lines.append(f"  per_rank_area={d['per_rank_area']}")

    cp = agg.get("comm_plan")
    if cp:
        lines.append("")
        lines.append(
            f"comm plan [{cp.get('planner') or 'static'}] "
            f"builds={cp['builds']}"
        )
        for s in cp["stages"]:
            ratio = s.get("wire_ratio")
            ratio_s = f", wire_ratio={ratio:.3f}" if ratio is not None else ""
            lines.append(
                f"  stage {s['stage']}: {s['lowering']} "
                f"payload={s['payload_rows']} wire={s['wire_rows']} rows "
                f"(padding={s['padding_rows']}{ratio_s})"
            )

    st = agg.get("attn_step")
    if st:
        lines.append("")
        lines.append(
            f"attn steps={st['steps']} backend={st['backend']} "
            f"overlap_degree={st['overlap_degree']} "
            f"blocks=({st['block_q']}, {st['block_k']})"
        )
        lines.append(
            f"  comm: payload={_fmt_bytes(st['payload_bytes_total'])} "
            f"wire={_fmt_bytes(st['wire_bytes_total'])} "
            f"padding_waste={_fmt_bytes(st['padding_bytes_total'])}"
        )
        if st.get("band_elems") is not None:
            padded, band = st["padded_elems"], st["band_elems"]
            eff = band / padded if padded else 1.0
            lines.append(
                f"  kernel work: band_elems={band} padded_elems={padded} "
                f"(grid efficiency {eff:.1%}); "
                f"est_flops_fwd={st['est_flops_fwd']:.3g} "
                f"executed={st['padded_flops_fwd']:.3g}"
            )
        if st.get("bwd_modes"):
            split_count = st["bwd_modes"].get("split", 0)
            fused_count = st["bwd_modes"].get("fused", 0)
            lines.append(
                f"  backward: mode={st['bwd_mode']} "
                f"(fused={fused_count} split={split_count} steps) — fused "
                "one-pass shares the S/P recompute across dq/dk/dv "
                "(5 vs 7 tile matmuls; MAGI_ATTENTION_BACKEND_FFA_BWD)"
            )
        if st.get("wall_ms_last") is not None:
            lines.append(
                f"  wall: last={st['wall_ms_last']:.1f} ms "
                f"min={st['wall_ms_min']:.1f} ms "
                "(first call includes trace+compile; per-stage device time "
                "lives in the xprof spans named by each record's "
                "xprof_scope)"
            )

    fp = agg.get("ffa_plans")
    if fp:
        lines.append("")
        ratio = fp["padding_ratio"]
        lines.append(
            f"ffa plans={fp['plans']} band_elems={fp['band_elems']} "
            f"padded_elems={fp['padded_elems']}"
            + (f" (padding_ratio={ratio:.3f})" if ratio else "")
        )
        if fp.get("executed_ratio") is not None:
            clamp = fp.get("extent_clamp")
            lines.append(
                f"  extent clamp[{'on' if clamp else 'off'}]: "
                f"executed_elems={fp['executed_elems']} "
                f"(executed/band={fp['executed_ratio']:.3f} vs "
                f"padded/band={ratio:.3f})"
                if ratio is not None
                else f"  executed_elems={fp['executed_elems']}"
            )
        if fp.get("frag_histogram"):
            hist = " ".join(
                f"{k}={v}" for k, v in fp["frag_histogram"].items()
            )
            lines.append(f"  fragmentation (slices by cover ratio): {hist}")

    md = agg.get("mixed_dispatch")
    if md:
        lines.append("")
        lines.append(
            f"mixed dispatch splits={md['splits']} "
            f"(forced={md['forced']}): last "
            f"dense={md['num_dense']} slices @ {md['coarse_blocks']} + "
            f"frag={md['num_frag']} slices @ {md['fine_blocks']} "
            f"(score {md['single_score']} -> {md['split_score']})"
        )

    tp = agg.get("tile_policy")
    if tp:
        lines.append("")
        lines.append(
            f"tile policy [{tp['mode']}] picks={tp['picks']} "
            f"fwd={tp['fwd_blocks']} dq={tp['dq_blocks'] or 'inherit'} "
            f"dkv={tp['dkv_blocks'] or 'inherit'} "
            f"(scored {tp['candidates_scored']} candidates)"
        )

    rc = agg.get("runtime_cache")
    if rc:
        lines.append("")
        lines.append(
            f"runtime cache: hits={rc['hits']} misses={rc['misses']} "
            f"evictions={rc['evictions']} size={rc['size']}/{rc['maxsize']}"
        )

    pv = agg.get("plan_verify")
    if pv:
        lines.append("")
        fired = ",".join(pv["fired_rules"]) or "none"
        wall = (
            f" wall_last={pv['wall_ms_last']:.1f} ms "
            f"total={pv['wall_ms_total']:.1f} ms"
            if pv.get("wall_ms_last") is not None
            else ""
        )
        lines.append(
            f"plan verify [{pv.get('planner') or '?'}] runs={pv['runs']} "
            f"rules={','.join(pv.get('rules_run') or [])} "
            f"errors={pv['errors_total']} warnings={pv['warnings_total']} "
            f"fired={fired}{wall}"
        )

    ka = agg.get("kernel_audit")
    if ka:
        lines.append("")
        fired = ",".join(ka["fired_rules"]) or "none"
        lines.append(
            f"kernel audit runs={ka['runs']} kernels={ka['kernels']} "
            f"configs={ka['configs']} "
            f"rules={','.join(ka.get('rules_run') or [])} "
            f"errors={ka['errors_total']} warnings={ka['warnings_total']} "
            f"fired={fired}"
        )
        if ka.get("vmem_worst_bytes") is not None:
            lines.append(
                f"  vmem worst: {_fmt_bytes(ka['vmem_worst_bytes'])} of "
                f"{_fmt_bytes(ka['vmem_allowed_bytes'])} allowed "
                f"({ka['vmem_worst_config']})"
            )

    rs = agg.get("resilience")
    if rs:
        lines.append("")
        lines.append(
            f"resilience: injected={rs['injected']} "
            f"guard_trips={rs['guard_trips']} "
            f"fallback_hops={rs['fallback_hops']} retries={rs['retries']} "
            f"recovered={rs['recovered']}"
        )
        for site, n in rs["hops_by_site"].items():
            lines.append(f"  hops at {site}: {n}")

    sv = agg.get("serve")
    if sv:
        lines.append("")
        lines.append(
            f"serving steps={sv['steps']} admitted={sv['admitted_total']} "
            f"evicted={sv['evicted_total']} "
            f"completed={sv['completed_total']}"
        )
        lines.append(
            f"  tokens: prefill={sv['prefill_tokens_total']} "
            f"decode={sv['decode_tokens_total']}"
        )
        if sv.get("occupancy_mean") is not None:
            lines.append(
                f"  occupancy mean={sv['occupancy_mean']:.2f}; "
                f"pages_in_use last={sv['pages_in_use_last']} "
                f"max={sv['pages_in_use_max']}"
            )
        if sv.get("wall_ms_mean") is not None:
            lines.append(
                f"  wall per step: mean={sv['wall_ms_mean']:.1f} ms "
                f"max={sv['wall_ms_max']:.1f} ms"
            )
        if sv.get("kv_dtype") is not None:
            lines.append(
                f"  scale: kv_dtype={sv['kv_dtype']} shards={sv['shards']} "
                f"spec_k={sv['spec_k']}"
            )
        if sv.get("accept_rate") is not None:
            lines.append(
                f"  speculative: accepted={sv['draft_accepted_total']}/"
                f"{sv['draft_attempted_total']} "
                f"(rate {sv['accept_rate']:.2f}, "
                f"{sv['accepted_per_tick']:.2f} tok/tick)"
            )

    ns = agg.get("nsa")
    if ns:
        lines.append("")
        backends = " ".join(f"{k}={v}" for k, v in ns["backends"].items())
        lines.append(
            f"nsa steps={ns['steps']} slc_backend={ns['slc_backend']} "
            f"({backends}) top_k={ns['top_k']} hk={ns['hk']} "
            f"n_qb={ns['n_qb']} l_slc={ns['l_slc']} d_stride={ns['d_stride']}"
        )
        ratio = ns.get("gather_savings_ratio")
        lines.append(
            f"  slc KV bytes: streamed={_fmt_bytes(ns['executed_bytes_total'])}"
            f" vs gathered={_fmt_bytes(ns['gathered_bytes_total'])}"
            + (f" (gather-free saves x{ratio:.2f})" if ratio else "")
        )

    ps = agg.get("plan_solve")
    if ps:
        lines.append("")
        planners = " ".join(f"{k}={v}" for k, v in ps["planners"].items())
        lines.append(
            f"plan solving: solves={ps['solves']} "
            f"(cold={ps['cold']} incremental={ps['incremental']}) "
            f"cache_hits={ps['cache_hits']} [{planners}]"
        )
        if ps.get("resolve_fraction") is not None:
            inc_s = (
                f"; incremental-only {ps['incremental_resolve_fraction']:.1%}"
                if ps.get("incremental_resolve_fraction") is not None
                else ""
            )
            lines.append(
                f"  rows re-solved: {ps['rows_resolved']}/{ps['rows_total']} "
                f"({ps['resolve_fraction']:.1%} of chunk rows{inc_s})"
            )
        if ps.get("wall_ms_total") is not None:
            lines.append(
                f"  solver wall: total={ps['wall_ms_total']:.1f} ms "
                f"mean={ps['wall_ms_mean']:.1f} ms"
            )
        if ps.get("two_level_solves"):
            lines.append(
                f"  two-level (dcn x ici) solves: {ps['two_level_solves']}"
            )

    pcp = agg.get("plan_control_plane")
    if pcp:
        lines.append("")
        srcs = " ".join(f"{k}={v}" for k, v in pcp["by_source"].items())
        lines.append(
            f"plan control plane: resolutions={pcp['resolutions']}"
            + (f" [{srcs}]" if srcs else "")
        )
        if pcp["store_reads"] or pcp["store_writes"]:
            miss_s = " ".join(
                f"{k}={v}" for k, v in pcp["store_miss_reasons"].items()
            )
            lines.append(
                f"  store: reads={pcp['store_reads']} "
                f"hits={pcp['store_hits']} misses={pcp['store_misses']}"
                + (f" ({miss_s})" if miss_s else "")
                + f" writes={pcp['store_writes']}"
                + (
                    f" orphans_removed={pcp['store_orphans_removed']}"
                    if pcp["store_orphans_removed"]
                    else ""
                )
            )
        if pcp["broadcasts"]:
            roles = " ".join(
                f"{k}={v}" for k, v in pcp["broadcast_by_role"].items()
            )
            lines.append(
                f"  broadcast: exchanges={pcp['broadcasts']} [{roles}] "
                f"exhausted={pcp['broadcast_exhausted']} "
                f"attempts={pcp['broadcast_attempts_total']} "
                f"backoff={pcp['broadcast_backoff_ms_total']:.0f} ms"
            )

    hc = agg.get("hier_comm")
    if hc:
        lines.append("")
        lines.append(
            f"hier comm plans={hc['plans']}: dcn_rows={hc['dcn_rows']} "
            f"vs flat {hc['flat_dcn_rows']} "
            f"(dedup x{hc['dcn_dedup_ratio']:.2f})"
        )

    bs = agg.get("backend_select")
    if bs:
        lines.append("")
        srcs = " ".join(f"{k}={v}" for k, v in bs["sources"].items())
        lines.append(
            f"backend selections={bs['selections']} (sources: {srcs})"
        )
        for dec, d in bs["by_decision"].items():
            choices = " ".join(
                f"{k}={v}" for k, v in sorted(d["choices"].items())
            )
            lines.append(f"  {dec}: {choices} (last={d['last_choice']})")

    rh = agg.get("rank_health")
    if rh:
        lines.append("")
        trans = (
            " ".join(f"{k}={v}" for k, v in rh["transitions"].items())
            or "none"
        )
        lines.append(
            f"rank health: observations={rh['observations']} "
            f"ranks={rh['ranks']} degraded_now={rh['degraded_now']} "
            f"(transitions: {trans})"
        )
        for r, d in rh["per_rank"].items():
            ewma = d.get("ewma_ms")
            ewma_s = f"{ewma:.1f}" if ewma is not None else "?"
            state = "DEGRADED" if d.get("degraded") else "healthy"
            lines.append(
                f"  rank {r}: ewma={ewma_s} ms "
                f"capacity={d.get('capacity')} [{state}]"
            )

    sr = agg.get("step_retry")
    if sr:
        lines.append("")
        froms = " ".join(
            f"{k}={v}" for k, v in sr["by_from_backend"].items()
        )
        errs = " ".join(f"{k}={v}" for k, v in sr["by_error"].items())
        lines.append(
            f"step retries={sr['events']} quarantines={sr['quarantines']} "
            f"(from: {froms}) (errors: {errs})"
        )
        last = sr.get("last") or {}
        if last.get("from_backend") is not None:
            lines.append(
                f"  last: {last.get('stage')} attempt={last.get('attempt')} "
                f"{last.get('from_backend')} -> {last.get('to_backend')} "
                f"({last.get('error')})"
            )

    so = agg.get("store")
    if so:
        lines.append("")
        hist = " ".join(f"{k}={v}" for k, v in so["history"].items()) or "none"
        lines.append(f"store [{so['dir']}]: history: {hist}")
        if so.get("rank_health_rows") or so.get("quarantine_rows"):
            lines.append(
                f"  degraded ranks: rank_health_rows="
                f"{so['rank_health_rows']} "
                f"quarantine_rows={so['quarantine_rows']}"
            )
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "paths", nargs="*",
        help="telemetry JSONL files or directories containing them",
    )
    ap.add_argument(
        "--json", action="store_true",
        help="print the aggregate as JSON instead of the text summary",
    )
    ap.add_argument(
        "--store", metavar="DIR",
        help="also summarize a persistent telemetry store directory "
             "(store.json + history-*.jsonl) as the 'store' section",
    )
    ap.add_argument(
        "--schema", action="store_true",
        help="print the --json section/field documentation and exit",
    )
    args = ap.parse_args(argv)
    if args.schema:
        print(json.dumps(SECTION_SCHEMAS, indent=2))
        return 0
    if not args.paths and not args.store:
        ap.error("paths required (or --store / --schema)")
    records = load_records(args.paths)
    if not records and not args.store:
        print("no telemetry records found", file=sys.stderr)
        return 1
    agg = aggregate(records)
    if args.store:
        agg["store"] = aggregate_store(args.store)
    if args.json:
        print(json.dumps(agg, indent=2))
    else:
        print(format_summary(agg))
    return 0


if __name__ == "__main__":
    sys.exit(main())
