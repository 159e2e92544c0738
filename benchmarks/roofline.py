import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ must precede jax import: the probes lower on the 16x16 production mesh.

"""Roofline analysis (deliverable g): three terms per (arch x shape).

Sources:
  * probe compiles — XLA cost_analysis counts lax.scan bodies ONCE, so the
    full-model dry-run FLOPs under-count deep stacks.  We therefore lower
    *unrolled* probe configs (every layer group at 1 and at 2 repeats; the
    zoo unrolls groups with <=4 repeats) and reconstruct:
        m_full = m_base + sum_g body_g * repeats_g,
        body_g = m(probe_g) - m(probe_0),   m_base = m(probe_0) - sum body_g
    This applies to per-device FLOPs, bytes accessed, and collective bytes
    alike.  cost_analysis is PER-DEVICE on this backend (verified against a
    hand-counted sharded matmul), so global = per_device * n_devices.
  * hardware constants — TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM,
    ~50 GB/s/link ICI (core.types.TPU_V5E).

Terms (seconds, per training/serving step):
  compute    = flops_global / (chips * peak)
  memory     = bytes_global / (chips * hbm_bw)
  collective = coll_bytes_global / (chips * link_bw)

plus MODEL_FLOPS (6*N*D dense / 6*N_active*D MoE; 2*N*D prefill; 2*N*B
decode) and the MODEL/HLO ratio.

Writes experiments/roofline/<arch>__<shape>.json.  Run standalone:
  PYTHONPATH=src python -m benchmarks.roofline [--arch A] [--shape S]
"""
import argparse
import dataclasses
import json
import time
from typing import Dict, List, Tuple

import jax

from repro.configs import SHAPES, cell_is_supported, get_config, list_archs
from repro.configs.base import ArchConfig
from repro.core.types import TPU_V5E
from repro.launch import dryrun
from repro.launch.mesh import make_production_mesh
from repro.models import model_zoo as zoo

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "roofline")


# ---------------------------------------------------------------------------
# Layer-group probe plans
# ---------------------------------------------------------------------------
def group_repeats(cfg: ArchConfig) -> Dict[str, int]:
    """Group name -> repeats in the full model."""
    if cfg.family == "audio":
        return {"enc": cfg.encoder_layers, "dec": cfg.num_layers}
    from repro.models.transformer import layer_plan
    return {g.name: g.repeats for g in layer_plan(cfg)}


def cfg_with_repeats(cfg: ArchConfig, reps: Dict[str, int]) -> ArchConfig:
    if cfg.family == "audio":
        return dataclasses.replace(cfg, encoder_layers=reps["enc"],
                                   num_layers=reps["dec"])
    if cfg.family == "hybrid":
        p = cfg.shared_attn_period
        n = p * reps.get("hybrid", 0) + reps.get("tail", 0)
        return dataclasses.replace(cfg, num_layers=n)
    if cfg.local_global_period:
        return dataclasses.replace(
            cfg, num_layers=cfg.local_global_period * reps["localglobal"])
    if cfg.moe is not None:
        fd = reps.get("dense_head", 0)
        return dataclasses.replace(
            cfg, num_layers=fd + reps["moe_body"],
            moe=dataclasses.replace(cfg.moe, first_dense_layers=fd))
    # single-group families (dense/ssm/vlm): whatever the group is named
    (only_group,) = reps.values()
    return dataclasses.replace(cfg, num_layers=only_group)


def probe_plan(cfg: ArchConfig) -> Tuple[Dict[str, int], List[Dict[str, int]]]:
    """(full repeats, probe repeat-maps).  probe[0] = all groups at 1."""
    full = group_repeats(cfg)
    base = {g: 1 for g in full}
    probes = [base]
    for g in full:
        if full[g] > 1:
            probes.append({**base, g: 2})
    return full, probes


# ---------------------------------------------------------------------------
# MODEL_FLOPS
# ---------------------------------------------------------------------------
def param_counts(cfg: ArchConfig) -> Tuple[int, int]:
    """(total, active) non-embedding params."""
    abs_params = zoo.init_params(cfg, jax.random.PRNGKey(0), abstract=True)
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(abs_params)[0]:
        keys = "/".join(str(getattr(p, "key", "")) for p in path)
        if "embed" in keys or "lm_head" in keys:
            continue
        total += leaf.size
    active = total
    if cfg.moe is not None:
        moe_layers = cfg.num_layers - cfg.moe.first_dense_layers
        per_expert = 3 * cfg.d_model * cfg.moe.d_ff_expert
        inactive = (cfg.moe.num_experts - cfg.moe.experts_per_token) * \
            per_expert * moe_layers
        active = total - inactive
    return total, active


def model_flops(cfg: ArchConfig, shape) -> float:
    _, active = param_counts(cfg)
    if shape.kind == "train":
        return 6.0 * active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * active * shape.global_batch * shape.seq_len
    return 2.0 * active * shape.global_batch        # one token per request


# ---------------------------------------------------------------------------
# Probe measurement
# ---------------------------------------------------------------------------
def measure(cfg: ArchConfig, shape, mesh) -> Dict[str, float]:
    fn, args, in_sh, donate = dryrun.build_step(cfg, shape, mesh)
    with mesh:
        compiled = jax.jit(fn, in_shardings=in_sh,
                           donate_argnums=donate).lower(*args).compile()
        cost = compiled.cost_analysis()
        coll = dryrun.collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(coll["total_bytes"]),
            "convert": float(dryrun.convert_bytes(compiled.as_text())),
            "coll_by_kind": coll["bytes"]}


def reconstruct(cfg: ArchConfig, shape, mesh) -> Dict[str, float]:
    full, probes = probe_plan(cfg)
    ms = [measure(cfg_with_repeats(cfg, p), shape, mesh) for p in probes]
    base_keys = ("flops", "bytes", "coll", "convert")
    m0 = ms[0]
    bodies: Dict[str, Dict[str, float]] = {}
    idx = 1
    for g in full:
        if full[g] > 1:
            bodies[g] = {k: max(0.0, ms[idx][k] - m0[k]) for k in base_keys}
            idx += 1
        else:
            bodies[g] = {k: 0.0 for k in base_keys}
    out = {}
    for k in base_keys:
        # probe_0 contains every group once; add (repeats-1) more bodies.
        out[k] = m0[k] + sum(bodies[g][k] * (full[g] - 1) for g in full
                             if full[g] > 1)
    out["coll_by_kind"] = m0["coll_by_kind"]
    out["probes"] = len(probes)
    return out


def run_cell(arch: str, shape_name: str, force: bool = False) -> dict:
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{arch}__{shape_name}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_is_supported(cfg, shape)
    rec = {"arch": arch, "shape": shape_name}
    if not ok:
        rec.update(status="skipped", reason=why)
    else:
        t0 = time.time()
        try:
            mesh = make_production_mesh(multi_pod=False)
            chips = mesh.devices.size
            m = reconstruct(cfg, shape, mesh)
            flops_g = m["flops"] * chips
            bytes_g = m["bytes"] * chips
            coll_g = m["coll"] * chips
            # TPU-adjusted bytes: remove XLA:CPU's bf16-emulation converts
            # (f32 output + bf16 input = 1.5x output bytes) — see
            # dryrun.convert_bytes.
            bytes_adj_g = max(bytes_g - 1.5 * m["convert"] * chips,
                              0.25 * bytes_g)
            t_comp = flops_g / (chips * TPU_V5E.peak_flops_bf16)
            t_mem = bytes_g / (chips * TPU_V5E.hbm_bandwidth)
            t_mem_adj = bytes_adj_g / (chips * TPU_V5E.hbm_bandwidth)
            t_coll = coll_g / (chips * TPU_V5E.ici_link_bandwidth)
            terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
            dominant = max(terms, key=terms.get)
            mf = model_flops(cfg, shape)
            total_p, active_p = param_counts(cfg)
            rec.update(
                status="ok", chips=chips,
                hlo_flops_global=flops_g, hlo_bytes_global=bytes_g,
                collective_bytes_global=coll_g,
                coll_by_kind_per_dev=m["coll_by_kind"],
                compute_s=t_comp, memory_s=t_mem, memory_s_tpu_adj=t_mem_adj,
                collective_s=t_coll, dominant=dominant,
                model_flops=mf, model_hlo_ratio=mf / max(flops_g, 1.0),
                params_total=total_p, params_active=active_p,
                roofline_fraction=t_comp / max(t_comp, t_mem, t_coll),
                probe_compiles=m["probes"],
                wall_s=round(time.time() - t0, 1),
            )
        except Exception as e:  # noqa: BLE001
            import traceback
            rec.update(status="error", error=f"{type(e).__name__}: {e}",
                       traceback=traceback.format_exc()[-1500:])
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    archs = [args.arch] if args.arch else list(list_archs())
    shapes = [args.shape] if args.shape else list(SHAPES)
    for arch in archs:
        for shape in shapes:
            rec = run_cell(arch, shape, args.force)
            if rec["status"] == "ok":
                print(f"{arch:24s} {shape:12s} dominant={rec['dominant']:10s}"
                      f" comp={rec['compute_s']:.3e}s"
                      f" mem={rec['memory_s']:.3e}s"
                      f" coll={rec['collective_s']:.3e}s"
                      f" model/hlo={rec['model_hlo_ratio']:.2f}", flush=True)
            else:
                print(f"{arch:24s} {shape:12s} {rec['status']}: "
                      f"{rec.get('reason', rec.get('error', ''))[:80]}",
                      flush=True)


if __name__ == "__main__":
    from repro.exec.jax_cache import use_persistent_cache
    use_persistent_cache()
    main()
