#!/bin/bash
# Full BASELINE ladder as PER-ROW driver invocations: one process per
# row, each appending its JSON line to the output file as it lands --
# a failed row takes out only itself instead of the rest of the
# ladder (the reference's sweep protocol similarly runs one mpiexec
# per configuration, scripts/nccl_combined.sh:48-176).
#
# Usage: scripts/ladder.sh [OUTPUT.jsonl]
set -u
cd "$(dirname "$0")/.."
OUT=${1:-LADDER.jsonl}

ROWS=(
  cg_iters_per_sec_poisson2d_n2048_f32
  cg_xla_iters_per_sec_poisson2d_n2048_f32
  cg_iters_per_sec_poisson2d_n2048_mixed
  cg_iters_per_sec_poisson2d_n2048_bf16
  cg_iters_per_sec_poisson2d_n2048_bf16rr
  cg_pipelined_iters_per_sec_poisson2d_n2048_f32
  cg_iters_per_sec_poisson3d_n128_f32
  cg_pipelined_iters_per_sec_poisson3d_n128_f32
  cg_iters_per_sec_poisson3d_n256_f32
  cg_iters_per_sec_poisson3d_n256_mixed
  cg_dist1_iters_per_sec_poisson2d_n2048_f32
  cg_iters_per_sec_irregular_n500k_d16_f32
  cg_coo_iters_per_sec_irregular_n500k_d16_f32
  cg_iters_per_sec_poisson3d_n128_petsc_f64
  cg_iters_per_sec_poisson3d_n128_hostnative_f64
  cg_iters_per_sec_poisson3d_n512_f32_dia
  cg_iters_per_sec_poisson3d_n512_mixed_dia
  cg_iters_per_sec_poisson3d_n512_bf16rr_dia
  cg_iters_per_sec_poisson3d_n256_bf16rr_dia
)

for row in "${ROWS[@]}"; do
  echo "# ladder row: $row" >&2
  timeout 900 python bench.py --full --row "$row" >> "$OUT"
  rc=$?
  if [ $rc -ne 0 ]; then
    echo "{\"metric\": \"$row\", \"skipped\": true, \"rc\": $rc}" >> "$OUT"
  fi
done
echo "# ladder complete: $(grep -c '"metric"' "$OUT") rows in $OUT" >&2
