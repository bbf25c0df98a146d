"""Print the bench_all.json training sweep as a markdown table, with
its capture date, plus the one-line summaries of the CPU/simulator
snapshots (BENCH_{search,mp,serve}.json).

  python tools/perf_report.py

The table describes the commit bench_all.json was captured at, not the
current code: README "Measured performance" says so and no longer
carries it.
"""

import json
import math
import os
import sys

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")

LABELS = {
    "transformer": "Transformer encoder (s512, 6L)",
    "alexnet": "AlexNet/CIFAR-10",
    "inception": "Inception-v3 299px",
    "nmt_lstm": "NMT LSTM (s40)",
    "dlrm": "DLRM",
}

# dlrm's table size is preset-dependent (bench.py vocab map) — label
# from the RECORDED preset so a small-preset capture can't masquerade
# as the 1M-row full config (r4 review finding)
DLRM_PRESET_LABEL = {
    "full": "DLRM (26x 1M-row tables)",
    "small": "DLRM (26x 100k-row tables)",
    "tiny": "DLRM (8x 1k-row tables)",
}
ORDER = ["transformer", "alexnet", "inception", "nmt_lstm", "dlrm"]

BEGIN = "| Config | samples/s/chip | utilization | ms/step |"


def row(model, entry):
    e = entry.get("extra", {})
    util = e.get("mfu")
    basis = e.get("util_basis", "mfu")
    vsb = entry.get("vs_baseline")
    if basis != "mfu":
        util_s = f"{e.get('hbm_util', 0):.2f} HBM ({vsb:.2f}x target)"
    elif "hbm_util" in e:
        # roofline WAS captured but MFU won the max() — show both
        util_s = f"{e['hbm_util']:.2f} HBM ({vsb:.2f}x target, mfu basis)"
    elif model == "dlrm":
        # bandwidth-bound: an MFU-basis number with no roofline capture
        # is meaningless — say so rather than print 0.00
        util_s = "bandwidth-bound (roofline capture pending)"
    else:
        bold = "**" if vsb and vsb >= 1.0 else ""
        util_s = f"{bold}{util:.2f}{bold} ({vsb:.2f}x target)"
    stale = " *(stale)*" if e.get("stale") else ""
    label = LABELS.get(model, model)
    if model == "dlrm":
        label = DLRM_PRESET_LABEL.get(e.get("preset"), label)
    if e.get("batch"):
        label += f" b{e['batch']}"
    return (f"| {label}{stale} | "
            f"{entry.get('value', 0):,.0f} | {util_s} | "
            f"{e.get('ms_per_step', 0):.1f} |")


def build_table(bench):
    lines = [BEGIN, "|---|---|---|---|"]
    captured = set()
    for m in ORDER:
        entry = bench.get(m)
        if not entry:
            lines.append(f"| {LABELS.get(m, m)} | — | unmeasured | — |")
            continue
        lines.append(row(m, entry))
        c = entry.get("extra", {}).get("captured")
        if c:
            captured.add(c[:10])
    note = (f"Captured {', '.join(sorted(captured)) or 'n/a'} "
            f"(`bench_all.json`): these numbers predate current code.")
    note += search_line()
    note += mp_line()
    note += serve_line()
    return "\n".join(lines), note


def search_line() -> str:
    """Strategy-search throughput sentence from BENCH_search.json,
    keyed to the machine fingerprint of the shared cost cache
    (search/cost_cache.py) — the committed numbers are attributable to
    one machine + cost-model state without re-measuring anything
    (tools/search_bench.py refreshes the JSON)."""
    try:
        with open(os.path.join(ROOT, "BENCH_search.json")) as f:
            text = f.read()
        b = None
        try:  # pre-PR-11 whole-file dict form
            doc = json.loads(text)
            if isinstance(doc, dict) and "speedup" in doc:
                b = {"speedup": doc["speedup"], **doc}
        except json.JSONDecodeError:
            pass
        if b is None:  # merge-by-metric JSONL (tools/_bench_io.py)
            sys.path.insert(0, os.path.dirname(
                os.path.abspath(__file__)))
            from _bench_io import record_map
            r = record_map(
                os.path.join(ROOT, "BENCH_search.json")).get(
                "search_delta_speedup")
            if r is not None:
                b = {"speedup": r["value"], **r.get("extra", {})}
        if b is None:
            return ""
        return (f" Strategy search: "
                f"{b['proposals_per_sec_delta']:,.0f} proposals/s with "
                f"delta simulation vs {b['proposals_per_sec_full']:,.0f} "
                f"full ({b['speedup']:.1f}x, `BENCH_search.json`, "
                f"fingerprint `{b.get('fingerprint', 'n/a')}`).")
    except (OSError, json.JSONDecodeError, KeyError):
        return ""


def mp_line() -> str:
    """Mixed-precision sentence from BENCH_mp.json (tools/mp_bench.py):
    the simulator-priced bf16-vs-f32 step-makespan reductions and, when
    a TPU was attached at capture time, the wall-clock speedup."""
    try:
        with open(os.path.join(ROOT, "BENCH_mp.json")) as f:
            b = json.load(f)
        s = b["simulated"]
        line = (f" Mixed precision (bf16 compute, f32 masters): "
                f"{s['transformer']['reduction']:.2f}x simulated "
                f"step-makespan reduction on the transformer, "
                f"{s['dlrm']['reduction']:.2f}x on DLRM")
        wall = b.get("wallclock")
        if wall:
            line += (f"; {wall['speedup']:.2f}x wall-clock "
                     f"({wall['bfloat16']['tokens_per_sec']:,.0f} tok/s)")
        return line + " (`BENCH_mp.json`)."
    except (OSError, json.JSONDecodeError, KeyError):
        return ""


def serve_line() -> str:
    """Serving sentence from BENCH_serve.json (merge-by-metric JSONL
    via the shared reader, which also tolerates the legacy formats):
    the headline multipliers of the serving stack — prefix-cache
    prefill reduction, speculative step reduction, disaggregated
    TPOT-p99, and the multi-replica router's goodput-under-SLO gain
    (tools/serve_bench.py refreshes the JSON per --workload)."""
    try:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from _bench_io import record_map
        recs = record_map(os.path.join(ROOT, "BENCH_serve.json"))
        parts = []
        pieces = (
            ("serve_prefill_token_reduction",
             "{v:.1f}x prefix-cache prefill reduction"),
            ("serve_decode_step_reduction",
             "{v:.1f}x speculative decode steps"),
            ("serve_kv_page_capacity",
             "{v:.1f}x int8 KV pages/byte"),
            ("serve_disagg_tpot_p99_reduction",
             "{v:.1f}x disaggregated TPOT p99"),
            ("serve_router_goodput_gain",
             "{v:.1f}x routed goodput-under-SLO vs round-robin"),
            ("serve_lora_goodput_gain",
             "{v:.1f}x batched-LoRA goodput vs weight swap"),
            ("serve_fabric_wall_goodput_gain",
             "{v:.1f}x threaded wall-clock goodput (wall==virtual)"),
            ("serve_host_tier_goodput_gain",
             "{v:.1f}x host-tier goodput vs eviction"),
            ("serve_boot_warm_speedup",
             "{v:.1f}x warm replica boot"),
            ("serve_mesh2d_goodput_gain",
             "{v:.1f}x 2-D mesh goodput vs best 1-D"),
        )
        for key, fmt in pieces:
            r = recs.get(key)
            if r is not None:
                parts.append(fmt.format(v=float(r["value"])))
        lora = recs.get("serve_lora_goodput_gain")
        if lora is not None:
            tenants = lora.get("extra", {}).get("tenants")
            if tenants:
                idx = [i for i, p in enumerate(parts)
                       if "batched-LoRA" in p]
                if idx:
                    parts[idx[0]] += f" ({int(tenants)} tenants)"
        # the boot record's cold-vs-warm seconds + programs restored
        # (the AOT program-cache A/B, serve_bench --workload boot)
        boot = recs.get("serve_boot_warm_speedup")
        if boot is not None:
            e = boot.get("extra", {})
            idx = [i for i, p in enumerate(parts)
                   if "warm replica boot" in p]
            if idx and "cold_ready_s" in e and "warm_ready_s" in e:
                parts[idx[0]] += (
                    f" ({e['cold_ready_s']:.2f}s cold -> "
                    f"{e['warm_ready_s']:.2f}s, "
                    f"{int(e.get('programs_restored', 0))} programs "
                    f"restored)")
        # the 2-D mesh record's searched shape (serve_bench
        # --workload mesh2d): which (t, r) the walk picked
        mesh = recs.get("serve_mesh2d_goodput_gain")
        if mesh is not None:
            e = mesh.get("extra", {})
            idx = [i for i, p in enumerate(parts)
                   if "2-D mesh goodput" in p]
            if idx and "searched_tensor" in e:
                parts[idx[0]] += (
                    f" (t={int(e['searched_tensor'])} x "
                    f"r={int(e['searched_replicas'])} over "
                    f"{int(e.get('devices', 0))} devices)")
        # SLO attainment from the EXPORTED pool registry gauge the
        # router workload recorded (serve_pool_slo_attainment — not an
        # ad-hoc stat string), and the worst simulator drift ratio
        # from the base workload's exported drift snapshot — the PR 10
        # render-from-metrics no-drift rule applied to the headline
        router = recs.get("serve_router_goodput_gain")
        if router is not None:
            att = router.get("extra", {}).get("slo_attainment_gauge")
            if att is None:
                att = router.get("extra", {}).get(
                    "slo_attainment_affinity")
            if att is not None:
                parts.append(f"{float(att):.0%} SLO attainment")
        base = recs.get("serve_decode_tokens_per_sec")
        if base is not None:
            drift = (base.get("extra", {}).get("telemetry", {})
                     or {}).get("drift_ratio_by_regime") or {}
            ratios = [float(v) for v in drift.values() if v]
            if ratios:
                worst = max(ratios, key=lambda r: abs(math.log(r))
                            if r > 0 else 0.0)
                parts.append(f"worst sim-drift ratio {worst:.2f}x "
                             f"over {len(ratios)} regimes")
        if not parts:
            return ""
        return (f" Serving: {', '.join(parts)} "
                f"(`BENCH_serve.json`).")
    except Exception:
        return ""


def main():
    with open(os.path.join(ROOT, "bench_all.json")) as f:
        bench = json.load(f)
    table, note = build_table(bench)
    print(table)
    print()
    print(note)
    return 0


if __name__ == "__main__":
    sys.exit(main())
