"""Assemble EXPERIMENTS.md from dry-run JSONs + benchmark outputs +
hand-written §Perf narrative.

    PYTHONPATH=src python -m repro.roofline.write_experiments
"""
from __future__ import annotations

import json
from pathlib import Path

from repro.roofline.report import (DRYRUN_DIR, dryrun_table, load_cells,
                                   roofline_table)

ROOT = Path(__file__).resolve().parents[3]


def _opt_cells():
    rows = []
    for f in sorted(DRYRUN_DIR.glob("*_opt*.json")):
        d = json.loads(f.read_text())
        if not d.get("ok"):
            rows.append(f"| {f.stem} | FAILED | | | |")
            continue
        t = d["terms"]
        rows.append(
            f"| {d['arch']} {d['shape']} | {d.get('tag','')} | "
            f"{t['compute_s']:.4f} | {t['collective_s']:.4f} | "
            f"{t['roofline_fraction']:.4f} |")
    return "\n".join(rows)


PERF_NARRATIVE = """\
### Methodology

No real TPU exists in this container, so the "profile" for each iteration is
the **compiled HLO** of the production-mesh dry-run: per-device FLOPs and
collective bytes from the loop-aware analyzer (`repro/roofline/hlo_analysis.py`
— it multiplies while-loop bodies by their `known_trip_count`, which XLA's
`cost_analysis()` does not), plus a per-instruction *top-collectives*
attribution (`--diagnose`) that names the jaxpr source of every collective.
Each iteration states a hypothesis with napkin math, changes one thing,
re-lowers, and records confirmed/refuted.

Three hillclimb cells were selected per the assignment rule:
* **worst roofline fraction**: `grok-1-314b x decode_32k` (0.0022)
* **most collective-bound**: `stablelm-3b x prefill_32k` (coll/compute = 47x)
* **paper-representative**: `qwen2.5-3b x decode_32k` (GQA dense target,
  D2SD serve_step = the paper's core workload)

### Iteration log (hypothesis -> change -> before -> after)

**It-1 — stablelm-3b prefill_32k: KV-cache writes were gather-scatters.**
Diagnosis: 2 x 9.6 GB/layer `all-gather(scatter)` — the KV write used a
general per-example-offset scatter (ragged decode support), which SPMD
cannot partition; it gathered the full K/V and cache. Hypothesis: prefill
always starts at offset 0, so a scalar-offset `dynamic_update_slice` is
partitionable along the kv_seq axis with ZERO communication (napkin: remove
~614 GB/step of gathers, leaving ~2x1.2 GB/layer TP all-reduces =>
collective 15.9 s -> ~2.7 s). Change: `pipeline.prefill` passes scalar
`cache_len=0`. **Measured: collective 15.85 s -> 3.80 s (-76 %), roofline
fraction 0.0073 -> 0.0306 (4.2x). CONFIRMED.**

**It-2 — qwen2.5-3b decode_32k: shard_map hygiene (check_vma) + bf16 merge.**
Diagnosis: per cycle, 2x320 MB TP all-reduces, 323 MB KV-SP LSE-merge psum,
160 MB `shard_map` all-gather. Hypotheses: (a) `check_vma=True` lets
shard_map prove the psum'd output replicated and skip its output gather;
(b) casting merge partials to bf16 halves the psum payload. Napkin: 27 ms ->
~19 ms. Change: spdecode check_vma=True + normalized-bf16 psum payload.
**Measured: 27.3 ms -> 27.3 ms. REFUTED.** Attribution of the new HLO shows
(a) the 160 MB gather is an **input** gather — q is heads-sharded by TP and
must be gathered to enter KV-sequence-parallel attention (minimal, not
removable); (b) the psum still moves 8.97 MB/layer in f32 — XLA reassociates
the convert above the all-reduce. Lesson: VMA hygiene is correctness
robustness, not traffic; qwen decode sits near its structural collective
floor at this batch/tree size (2 TP-ARs + q-gather + merge/psum ~ 16-27
MB/layer). Next lever recorded: reduce-scatter/all-gather decomposition of
the TP pair, or wider trees to amortize (K, gamma scaling).

**It-3 — grok-1-314b decode_32k: expert parallelism replaces pjit dispatch.**
Diagnosis: 5.8 GB/layer `all-gather(dot)` — the experts dim (8) does not
divide the model axis (16), so the sharding rule was dropped and SPMD
gathered **expert weights** to the tokens every layer; compute was also
inflated 58x. Hypothesis: in our TP layout tokens are replicated across the
model axis, so EP needs *no all_to_all at all*: each rank computes only its
own experts (f-sliced when M % E == 0) and ONE psum([T_loc, d]) ~ 14
MB/layer merges contributions (napkin: collective 14.7 s -> < 1 s given
weights resident). Change: `distributed/ep.py` (shard_map EP,
oracle-validated fwd+grad) wired as the default MoE path under a mesh.
**Measured: compute 3.22 s -> 0.056 s; collective 14.7 s -> 11.3 s.
PARTIALLY CONFIRMED** — the residual 11.3 s is the FSDP(data)-sharded
weights being re-laid-out into the EP arrangement every layer.

**It-4 — grok-1-314b decode_32k: EP with resident (non-FSDP) weights.**
Hypothesis: with MoE weights stored model-axis-resident the per-layer weight
relayout vanishes; expect collective ~ activation psums (~0.05-0.1 s), at
the cost of 39 GB/device weights — OVER the 16 GB v5e HBM, so this run
measures the communication floor; the deployable fix (documented, next
lever) is a 2-D resident layout [experts -> model, d_ff-slices -> data]
with tokens all-gathered across data (119 MB/layer, ~0.45 s/cycle).
**Measured: collective 14.7 s -> 3.05 s, fraction 0.0022 -> 0.0107 (4.9x).
PARTIALLY CONFIRMED** (better than baseline by 4.9x but 30x short of the
napkin floor — the residual is attention/router weight traffic, next
diagnosis target).

**It-5 — stablelm-3b prefill_32k: drop sequence-parallel activations.**
Hypothesis: the remaining 3 x ~600 MB/layer gathers are act_seq(model) <->
heads(model) resharding around attention; disabling SP (activations
replicated, pure heads-TP) removes them, leaving the 2 TP all-reduces
(napkin: 3.8 s -> ~2.6 s; memory rises ~B*S*d bf16 = 335 MB/dev).
**Measured: collective 3.80 s -> 2.55 s (napkin said 2.6), fraction 0.0306
-> 0.0456. CONFIRMED** — though compute rose 0.33 -> 0.60 s (elementwise
work no longer seq-split), a worthwhile trade while collectives dominate.
Cumulative on this cell: fraction 0.0073 -> 0.0456 (6.2x).

**It-6 — grok-1-314b train_4k: EP for training.** The EP path is
differentiable (shard_map + psum transposes to broadcast), so the same fix
applies to MoE train cells, where the baseline pjit dispatch both gathered
expert weights AND inflated compute.
**Measured: compute 1122.7 s -> 17.7 s (63x), collective 1473.6 s -> 48.2 s
(30.6x), roofline fraction 0.0072 -> 0.2191 (30x). CONFIRMED — the largest
single win of the study; 6ND-useful compute now runs at ~22 % of the
512-chip roofline for a 314B MoE.**

### Optimized-cell measurements

| cell | tag | compute_s | collective_s | roofline frac |
|---|---|---|---|---|
"""

HEADER = """\
# EXPERIMENTS — D2SD multi-pod JAX framework

Environment: single-CPU container; TPU v5e is the *target* (197 TFLOP/s
bf16, 819 GB/s HBM, ~50 GB/s/link ICI). Pallas kernels execute in
interpret mode; distribution is proven by lowering + compiling against
512 host devices (the multi-pod dry-run). Wall-clock numbers at paper scale
are therefore **roofline-modeled**; acceptance-length (alpha/TPF) numbers
are **measured** by running the real engine on trained small-scale models.

Contents: §Repro (paper tables) · §Dry-run · §Roofline · §Perf.
"""


def main():
    parts = [HEADER]

    bench = ROOT / "bench_output.txt"
    parts.append("\n## §Repro — paper-table reproductions\n")
    parts.append(
        "Measured on the trained small-scale study (see "
        "`repro/training/run_study.py`; target 4L/256d LM on the synthetic "
        "math/code/chat suites, drafters distilled per §3.4; alpha/TPF "
        "measured by running the real engine, speedups roofline-modeled at "
        "paper scale per Eq. 2). Full CSVs: `bench_output.txt`.\n")
    parts.append("""
**Findings vs the paper's claims:**

* **Lossless-ness (core property)**: greedy D2SD output == plain greedy
  target decoding token-for-token with arbitrary drafters, and sampled
  D2SD matches the target distribution to sampling noise (TV ~ 0.02).
  REPRODUCED exactly (tests/test_lossless.py).
* **Fig 2a calibration**: confidence bins track empirical accept rates
  near-diagonally, ECE ~ 0.04. REPRODUCED — the premise of Eq. 4 holds for
  block-diffusion drafters at our scale too.
* **Table 3 ordering**: D2SD > DFlash in BOTH alpha and speedup on every
  task and both temperatures; EAGLE-style AR chain reaches the longest
  alpha (9.1 avg greedy) yet loses wall-clock to its gamma-1 sequential
  drafter passes — the paper's "drafting tax" argument, REPRODUCED
  directionally (our absolute gaps are smaller: a 4M target and 400-step
  drafters sit in a weaker-agreement regime than Qwen3-8B + SpecForge).
* **Table 6 (the key ablation)**: reusing the fixed-anchor DFlash drafter
  as the second drafter yields ZERO alpha gain over single-chain (2.12 ->
  2.12 on math) — the variable-prefix extrapolation failure the paper
  predicts — while the Eq. 6/7-trained VP-Drafter lifts alpha (2.12 ->
  2.40). REPRODUCED cleanly; this isolates the paper's §3.4 contribution.
* **Table 7**: stacking a third VP level leaves alpha ~flat at our scale
  while the modeled speedup regresses (2.16x -> 2.08x) — the paper's
  cost/recovery asymmetry, REPRODUCED directionally.
* **Table 1 (scaling wall)**: TPF saturates with gamma on math
  (1.90/2.03/2.09/2.09 at gamma=4/8/12/16); code is predictable enough
  that gamma=16 has not hit the wall. Partially reproduced (the paper's
  decline at gamma>=24 needs per-gamma retrained drafters, a documented
  deviation).
* **Table 5 DEVIATION**: at our scale, K naive T=1 resamples BEAT the VP
  second draft (math alpha 2.59 vs 2.40). The paper's error-homogeneity
  argument presumes confident drafters whose resamples collapse onto the
  argmax path; our small drafter's categoricals are diffuse, so uniform
  resampling retains diversity. We report this honestly: the cascade
  machinery reproduces, but naive-K's *inferiority* is a property of the
  strong-drafter regime we cannot reach on CPU.
""")
    if bench.exists():
        parts.append("```\n" + bench.read_text()[-8000:] + "\n```\n")
    else:
        parts.append("*(run `python -m benchmarks.run` to regenerate)*\n")

    parts.append("\n## §Dry-run — 10 archs x 4 shapes x 2 meshes\n")
    for mesh in ("single", "multi"):
        parts.append(f"\n### mesh = {mesh} "
                     f"({'2x16x16 = 512 chips' if mesh == 'multi' else '16x16 = 256 chips'})\n")
        parts.append(dryrun_table(mesh))
        parts.append("")

    parts.append("""
Notes:
* `long_500k` is skipped by design for pure full-attention archs (quadratic
  at 524k ctx): qwen2.5, internlm2, gemma2 (global layers), stablelm, kimi,
  grok, llama-vision, whisper. It runs for recurrentgemma-2b + rwkv6-1.6b.
* `argument GB/dev` counts params + optimizer state + caches per device —
  the "fits" proof. kimi-k2 train at 256/512 chips exceeds a single v5e's
  16 GB (a 1T model realistically trains on >= 2k chips); the dry-run
  proves the sharding is coherent, and the bytes scale inversely with mesh
  size.
* FLOPs/collectives come from the loop-aware HLO analyzer (XLA's
  cost_analysis undercounts scan bodies by the trip count — verified and
  documented in `roofline/hlo_analysis.py`; raw cost_analysis flops are
  retained in each JSON for comparison).
""")

    parts.append("\n## §Roofline — per (arch x shape), single pod\n")
    parts.append("""
Terms per assignment: compute = HLO_FLOPs/dev / 197e12; memory =
HBM_bytes/dev / 819e9 (analytic traffic model — fusions hide byte counts
from HLO text; formulas in `roofline/analysis.py`); collective =
collective_bytes/dev / 50e9 (per-op (n-1)/n factors, all-reduce 2x).
`useful` = MODEL_FLOPS (6ND train / 2ND infer, N_active for MoE) over
global HLO FLOPs — the remat/redundancy waste detector. `roofline frac` =
useful-FLOP time / dominant-term time.
""")
    parts.append(roofline_table("single"))
    parts.append("""
Reading the table:
* **Every baseline cell is collective-dominated** — the §Perf iterations
  attack exactly that, cell by cell.
* train cells: useful-ratio ~0.6-0.7 = remat recompute (policy "full"); the
  `dots` policy trades memory for ~1.3x fewer FLOPs (knob:
  `--remat-policy dots`).
* decode cells: useful-ratio ~0.3 reflects tree-verify compute on
  speculative tokens later discarded — the algorithmic price speculation
  pays for latency; alpha converts it back into wall-clock wins.
* One sentence per dominant term is encoded in §Perf's iteration log.
""")

    parts.append("\n## §Perf — hillclimbing log\n")
    parts.append(PERF_NARRATIVE + _opt_cells() + "\n")
    parts.append("""
### Where this lands / beyond-paper deltas

* paper-faithful baseline (D2SD serve_step, naive pjit sharding) is
  recorded per cell above (tags: none);
* beyond-paper optimized versions are recorded under `_opt*` tags —
  separate rows, per the assignment's reproduce-then-optimize contract;
* implemented beyond-paper infrastructure this round: KV-sequence-parallel
  cascade decode (spdecode), replicated-token EP (ep.py), partitionable
  prefill KV writes, blockwise-int8 optimizer moments, int8+error-feedback
  gradient all-reduce, GPipe pod-axis pipeline wrapper, elastic
  checkpoint/restore.
* next levers (napkin-math'd, unimplemented): 2-D resident MoE weight
  layout for >=300B serving (25x on grok decode); reduce-scatter/all-gather
  TP decomposition for decode; ring attention for 32k prefill SP.
""")

    out = ROOT / "EXPERIMENTS.md"
    out.write_text("\n".join(parts))
    print(f"wrote {out} ({out.stat().st_size} bytes)")


if __name__ == "__main__":
    main()
