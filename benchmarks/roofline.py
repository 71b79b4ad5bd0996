"""§Roofline: three-term analysis per (arch x shape x mesh) from the
dry-run's compiled artifacts (launch/dryrun.py -> results/dryrun.json).

  compute   = matmul_flops_per_dev / peak_flops      (197 TFLOP/s bf16)
  memory    = mem_bytes_proxy_per_dev / hbm_bw       (819 GB/s)
  collective= collective_bytes_per_dev / link_bw     (50 GB/s/link ICI)

All three use the trip-count-corrected HLO accounting
(launch/hlo_analysis.py) — raw cost_analysis counts while bodies once.
MODEL_FLOPS is the analytic useful-flops estimate below; the ratio
MODEL_FLOPS / (HLO flops x chips) exposes remat/padding/redundancy waste.
"""
from __future__ import annotations

import json
import os

import numpy as np

from repro.configs.base import (
    AnnConfig,
    DCNConfig,
    DINConfig,
    DLRMConfig,
    LMConfig,
    SASRecConfig,
    SchNetConfig,
)
from repro.configs.registry import get_arch, get_shapes

PEAK_FLOPS = 197e12        # bf16 per chip
HBM_BW = 819e9             # bytes/s per chip
LINK_BW = 50e9             # bytes/s per ICI link

RESULTS = os.path.join(os.path.dirname(__file__), "results")


def _mlp_flops(dims) -> float:
    return 2.0 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))


def lm_active_params(cfg: LMConfig) -> float:
    """Matmul params on the per-token path (embed gather excluded,
    unembed included); MoE counts top-k + shared experts only."""
    d = cfg.d_model
    if cfg.attn_kind == "mla":
        m = cfg.mla
        qk = m.qk_nope_head_dim + m.qk_rope_head_dim
        attn = (d * m.q_lora_rank + m.q_lora_rank * cfg.n_heads * qk
                + d * m.kv_lora_rank + d * m.qk_rope_head_dim
                + m.kv_lora_rank * cfg.n_heads * m.qk_nope_head_dim
                + m.kv_lora_rank * cfg.n_heads * m.v_head_dim
                + cfg.n_heads * m.v_head_dim * d)
    else:
        attn = (d * cfg.n_heads * cfg.d_head * 2
                + d * cfg.n_kv_heads * cfg.d_head * 2)
    dense_mlp = (3 if cfg.mlp_kind == "swiglu" else 2) * d * cfg.d_ff
    n_dense = cfg.n_dense_layers if cfg.moe else cfg.n_layers
    total = n_dense * (attn + dense_mlp)
    if cfg.moe:
        mo = cfg.moe
        moe_mlp = (mo.top_k + mo.n_shared) * 3 * d * mo.d_ff \
            + d * mo.n_experts
        total += cfg.n_moe_layers * (attn + moe_mlp)
    total += d * cfg.vocab            # unembed
    if cfg.mtp:
        total += attn + 3 * d * (cfg.moe.d_ff * 8 if cfg.moe else cfg.d_ff)
    return float(total)


def lm_attention_flops(cfg: LMConfig, b: int, s: int, decode: bool):
    if cfg.attn_kind == "mla":
        qk = cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim
        dv = cfg.mla.v_head_dim
    else:
        qk = dv = cfg.d_head
    h = cfg.n_heads
    if decode:
        return 2.0 * b * s * h * (qk + dv) * cfg.n_layers
    return 2.0 * b * s * s * 0.5 * h * (qk + dv) * cfg.n_layers


def useful_flops(arch: str, cfg, family: str, shape) -> float:
    """Analytic MODEL_FLOPS per step (global, all chips)."""
    if family == "lm":
        n_act = lm_active_params(cfg)
        b = shape["batch"]
        s = shape["seq"]
        if shape.kind == "train":
            toks = b * s
            return 6.0 * n_act * toks + 3.0 * lm_attention_flops(
                cfg, b, s, False)
        if shape.kind == "prefill":
            return 2.0 * n_act * b * s + lm_attention_flops(cfg, b, s,
                                                            False)
        return 2.0 * n_act * b + lm_attention_flops(cfg, b, s, True)
    if family == "gnn":
        c: SchNetConfig = cfg
        dims = shape.dims
        if shape.name == "minibatch_lg":
            bn = dims["batch_nodes"]
            f1, f2 = dims["fanout"]
            n = bn * (1 + f1) + bn * f1 * f2
            e = bn * f1 + bn * f1 * f2
        elif shape.name == "molecule":
            n = dims["batch"] * dims["n_nodes"]
            e = dims["batch"] * dims["n_edges"]
        else:
            n, e = dims["n_nodes"], dims["n_edges"]
        d_feat = dims.get("d_feat", c.d_feat)
        h, r = c.d_hidden, c.n_rbf
        per_edge = 2.0 * (r * h + h * h) + 2 * h
        per_node = 2.0 * (2 * h * h)
        fwd = (e * per_edge + n * per_node) * c.n_interactions \
            + 2.0 * n * d_feat * h + 2.0 * n * (h * h // 2)
        return 3.0 * fwd       # train
    if family == "recsys":
        if shape.kind == "retrieval":
            b = shape["n_candidates"]
        else:
            b = shape["batch"]
        if isinstance(cfg, DLRMConfig):
            per = _mlp_flops((cfg.n_dense,) + tuple(cfg.bot_mlp)) + \
                _mlp_flops((378 + cfg.embed_dim,) + tuple(cfg.top_mlp)) + \
                2.0 * 27 * 27 * cfg.embed_dim
        elif isinstance(cfg, DCNConfig):
            d0 = cfg.n_dense + cfg.n_sparse * cfg.embed_dim
            per = cfg.n_cross_layers * 2.0 * d0 * d0 + \
                _mlp_flops((d0,) + tuple(cfg.mlp) + (1,))
        elif isinstance(cfg, DINConfig):
            d2 = 2 * cfg.embed_dim
            per = cfg.seq_len * _mlp_flops((4 * d2,) + tuple(cfg.attn_mlp)
                                           + (1,)) + \
                _mlp_flops((2 * d2,) + tuple(cfg.mlp) + (1,))
        else:  # SASRec
            d = cfg.embed_dim
            L = cfg.seq_len
            per = cfg.n_blocks * (L * 8.0 * d * d + 2.0 * L * L * d * 2)
            if shape.kind == "retrieval":
                per = 2.0 * d     # dot per candidate
        mult = 3.0 if shape.kind == "train" else 1.0
        return mult * per * b
    if family == "ann":
        c: AnnConfig = cfg
        b = shape["batch"]
        cap = int(np.ceil(2.5 * c.n / c.n_clusters))
        # top-level centroid scan + nprobe bucket scans
        return b * 2.0 * c.d * (c.n_clusters + c.nprobe * cap)
    raise ValueError(family)


def chips_of(mesh_name: str) -> int:
    return 512 if "multi" in mesh_name else 256


# ---------------------------------------------------------------------------
# analytic ann-scan roofline: f32 vs int8 corpus
# ---------------------------------------------------------------------------


def ann_scan_rows(b: int = 64, n: int = 1_000_000, d: int = 128,
                  k: int = 10) -> list:
    """Two-variant HBM-traffic model of the per-shard brute scan.

    The scan is bandwidth-bound (2*B*N*D flops over >= N*D*4 bytes is
    ~2B flops/byte at B=64 — far below the ~240 flops/byte ridge), so
    the variants differ almost purely in bytes moved:

      fused   : read db once; the running top-k lives in the revisited
                output block                      -> + B*k*8 bytes
      int8    : fused traffic with the corpus as per-row-scaled int8
                codes                             -> db bytes / 4

    Returns rows shaped like :func:`build_table`'s (arch/shape/mesh
    keys reused so the markdown table renders them), with
    ``roofline_frac`` = useful-byte fraction: db bytes / total bytes —
    the figure-of-merit the fused kernel raises."""
    flops = 2.0 * b * n * d
    db_f32 = n * d * 4.0
    db_int8 = n * d * 1.0 + n * 4.0          # codes + per-row scales
    out = b * k * 8.0                        # (dists f32, ids int32)
    variants = [
        ("fused", db_f32, db_f32 + out),
        ("fused-int8", db_int8, db_int8 + out),
    ]
    rows = []
    for name, useful_bytes, bytes_moved in variants:
        t_comp = flops / PEAK_FLOPS
        t_mem = bytes_moved / HBM_BW
        rows.append(dict(
            arch=f"ann-scan-{name}", shape=f"B{b}xN{n}xD{d}", mesh="1chip",
            status="ok", chips=1,
            gib_per_dev=bytes_moved / 2**30,
            gib_tpu_adj=bytes_moved / 2**30,
            t_compute_s=t_comp, t_memory_s=t_mem, t_collective_s=0.0,
            bottleneck="memory" if t_mem >= t_comp else "compute",
            model_flops=flops, hlo_flops_total=flops,
            useful_ratio=1.0,
            roofline_frac=useful_bytes / bytes_moved,
        ))
    return rows


def build_table(results_path=None):
    results_path = results_path or os.path.join(RESULTS, "dryrun.json")
    with open(results_path) as f:
        results = json.load(f)
    rows = []
    for key, rec in sorted(results.items()):
        arch, shape_name, mesh = key.split("|")
        if rec["status"] == "skipped":
            rows.append(dict(arch=arch, shape=shape_name, mesh=mesh,
                             status="skipped",
                             reason=rec.get("reason", "")))
            continue
        if rec["status"] != "ok":
            rows.append(dict(arch=arch, shape=shape_name, mesh=mesh,
                             status="error", reason=rec.get("error", "")))
            continue
        cfg, family = get_arch(arch)
        shape = next(s for s in get_shapes(family)
                     if s.name == shape_name)
        chips = chips_of(rec["mesh"])
        a = rec["analysis"]
        t_comp = a["matmul_flops"] / PEAK_FLOPS
        t_mem = a["mem_bytes_proxy"] / HBM_BW
        t_coll = a["collective_bytes"]["total"] / LINK_BW
        dom = max((("compute", t_comp), ("memory", t_mem),
                   ("collective", t_coll)), key=lambda kv: kv[1])[0]
        uf = useful_flops(arch, cfg, family, shape)
        hlo_total = a["matmul_flops"] * chips
        hoist = a.get("entry_f32_weight_convert_bytes", 0.0)
        rows.append(dict(
            arch=arch, shape=shape_name, mesh=mesh, status="ok",
            chips=chips,
            gib_per_dev=rec["memory"]["per_device_total"] / 2**30,
            gib_tpu_adj=(rec["memory"]["per_device_total"] - hoist) / 2**30,
            t_compute_s=t_comp, t_memory_s=t_mem, t_collective_s=t_coll,
            bottleneck=dom,
            model_flops=uf,
            hlo_flops_total=hlo_total,
            useful_ratio=(uf / hlo_total) if hlo_total else 0.0,
            roofline_frac=(
                t_comp / max(t_comp, t_mem, t_coll)
                if max(t_comp, t_mem, t_coll) > 0 else 0.0),
        ))
    return rows


def markdown(rows) -> str:
    out = ["| arch | shape | mesh | GiB/dev (tpu-adj) | compute s | "
           "memory s | collective s | bottleneck | MODEL/HLO | "
           "roofline frac |",
           "|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if r["status"] == "skipped":
            out.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | — | "
                       f"— | — | — | SKIP (listed) | — | — |")
            continue
        if r["status"] == "error":
            out.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | — | "
                       f"— | — | — | ERROR | — | — |")
            continue
        adj = r.get("gib_tpu_adj", r["gib_per_dev"])
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
            f"{r['gib_per_dev']:.1f} ({adj:.1f}) | "
            f"{r['t_compute_s']:.3g} | "
            f"{r['t_memory_s']:.3g} | {r['t_collective_s']:.3g} | "
            f"{r['bottleneck']} | {r['useful_ratio']:.2f} | "
            f"{r['roofline_frac']:.2f} |"
        )
    return "\n".join(out)


def run():
    # the analytic ann-scan rows need no dryrun artifacts; the compiled
    # (arch x shape x mesh) table is additive when dryrun.json exists
    rows = ann_scan_rows()
    try:
        rows += build_table()
    except FileNotFoundError:
        import sys

        print("roofline: no dryrun.json — emitting only the analytic "
              "ann-scan rows (run python -m repro.launch.dryrun --all "
              "for the compiled table)", file=sys.stderr)
    md = markdown(rows)
    os.makedirs(RESULTS, exist_ok=True)
    with open(os.path.join(RESULTS, "roofline.md"), "w") as f:
        f.write(md + "\n")
    ok = [r for r in rows if r["status"] == "ok"]
    from benchmarks.common import csv_row

    for r in ok:
        t_total = max(r["t_compute_s"], r["t_memory_s"],
                      r["t_collective_s"])
        csv_row(
            f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}",
            t_total * 1e6,
            f"bottleneck={r['bottleneck']};frac={r['roofline_frac']:.2f};"
            f"useful={r['useful_ratio']:.2f};gib={r['gib_per_dev']:.1f}",
        )
    print(f"\nroofline table written to {RESULTS}/roofline.md "
          f"({len(ok)} ok rows)")
    return rows


if __name__ == "__main__":
    run()
