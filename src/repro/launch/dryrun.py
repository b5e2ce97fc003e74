"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST run as its own process: the first two lines pin 512 placeholder host
devices before jax initializes. Produces, per cell:
  memory_analysis  (proves the program fits per-device HBM)
  cost_analysis    (HLO FLOPs / bytes for the roofline)
  collective bytes (parsed from the partitioned HLO)
written to experiments/dryrun/<cell>.json.

Usage:
  python -m repro.launch.dryrun --arch qwen2_0_5b --shape train_4k --mesh multi
  python -m repro.launch.dryrun --all          # every applicable cell
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"  # noqa: E402

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
from functools import partial  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import (ARCH_IDS, SHAPES, ShapeSpec, load_arch,  # noqa: E402
                                cell_is_applicable)
from repro.launch.mesh import auto_mesh, make_production_mesh, data_shards  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state  # noqa: E402
from repro.parallel.sharding import ShardRules, param_specs, rules_scope  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "s32": 4,
               "u64": 8, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


# ---------------------------------------------------------------------------
# input specs
# ---------------------------------------------------------------------------

def input_specs(cfg, shape: ShapeSpec) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    bf16 = jnp.bfloat16
    if shape.kind == "train":
        specs = {"tokens": jax.ShapeDtypeStruct((b, s), i32),
                 "labels": jax.ShapeDtypeStruct((b, s), i32)}
    elif shape.kind == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
    else:                                       # decode: one new token
        specs = {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}
    if cfg.family == "vlm" and shape.kind != "decode":
        specs["patches"] = jax.ShapeDtypeStruct(
            (b, cfg.n_prefix_tokens, cfg.d_model), bf16)
    if cfg.family == "encdec" and shape.kind != "decode":
        specs["frames"] = jax.ShapeDtypeStruct((b, s, cfg.d_model), bf16)
    return specs


def batch_sharding(specs, rules: ShardRules, n_batch_shards: int):
    def one(leaf):
        b = leaf.shape[0]
        axes = ["batch" if b % n_batch_shards == 0 and b >= n_batch_shards
                else None]
        axes += [None] * (leaf.ndim - 1)
        return rules.sharding(*axes)
    return jax.tree.map(one, specs)


def cache_sharding(cache_shapes, rules: ShardRules, n_batch_shards: int):
    from repro.parallel.sharding import axis_size, fit_spec
    msize = axis_size(rules, rules.resolve("model"))

    def one(path_tuple, leaf):
        name = "/".join(str(getattr(p, "key", p)) for p in path_tuple)
        bdim = leaf.shape[1]
        bax = "batch" if bdim % n_batch_shards == 0 and bdim >= n_batch_shards \
            else None
        if name.endswith(("k", "v")) and leaf.ndim == 5:      # (L,B,S,K,dh)
            # KV heads over model when divisible; else split-KV: shard the
            # SEQ dim (flash-decoding style) — required to fit 32k caches
            # when n_kv (2/8/12) doesn't divide the 16-way model axis
            if leaf.shape[3] % msize == 0:
                spec = [None, bax, None, "model", None]
            else:
                spec = [None, bax, "model", None, None]
        elif name.endswith(("ks", "vs")):                      # (L,B,S,K)
            if leaf.shape[3] % msize == 0:
                spec = [None, bax, None, "model"]
            else:
                spec = [None, bax, "model", None]
        elif "conv" in name:                                   # (L,B,W,C)
            spec = [None, bax, None, "model"]
        elif name.endswith("h"):                               # (L,B,lru)
            spec = [None, bax, "model"]
        elif "ssm" in name:                                    # (L,B,H,P,N)
            spec = [None, bax, "model", None, None]
        else:
            spec = [None] * leaf.ndim
        return NamedSharding(rules.mesh, fit_spec(rules, leaf.shape, spec))
    return jax.tree_util.tree_map_with_path(one, cache_shapes)


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def build_cell(cfg, shape: ShapeSpec, rules: ShardRules):
    """Returns (step_fn, arg_shapes, in_shardings, donate)."""
    mesh = rules.mesh
    nb = 1
    for a in rules.batch_axes:
        nb *= mesh.shape[a]
    if cfg.family == "moe":
        tokens = shape.global_batch * max(
            shape.seq_len if shape.kind == "train" else 1, 1)
        groups = nb if tokens % nb == 0 else 1
        cfg = dataclasses.replace(cfg, moe_groups=groups)

    key = jax.random.key(0)
    p_shapes = jax.eval_shape(lambda k: T.init_params(k, cfg), key)
    if shape.kind != "train":
        # serving weights are bf16 (no optimizer, no master copy)
        p_shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
            if s.dtype == jnp.float32 and len(s.shape) >= 2 else s, p_shapes)
    p_shard = param_specs(p_shapes, rules)
    specs = input_specs(cfg, shape)
    b_shard = batch_sharding(specs, rules, nb)

    if shape.kind == "train":
        opt_cfg = AdamWConfig()
        o_shapes = jax.eval_shape(lambda: init_opt_state(p_shapes))
        o_shard = param_specs(o_shapes, rules)

        def _fwd_params(params):
            if not cfg.bf16_param_gather:
                return params
            return jax.tree.map(
                lambda p: p.astype(jnp.bfloat16)
                if p.dtype == jnp.float32 and p.ndim >= 2 else p, params)

        def train_step(params, opt_state, batch):
            (loss, aux), grads = jax.value_and_grad(
                lambda p, c, b: T.train_loss(_fwd_params(p), c, b),
                has_aux=True)(params, cfg, batch)
            params, opt_state, stats = adamw_update(params, grads,
                                                    opt_state, opt_cfg)
            return params, opt_state, loss, stats["grad_norm"]

        return (train_step, (p_shapes, o_shapes, specs),
                (p_shard, o_shard, b_shard), (0, 1))

    if shape.kind == "prefill":
        def prefill_step(params, batch):
            return T.prefill(params, cfg, batch, max_len=shape.seq_len)
        return prefill_step, (p_shapes, specs), (p_shard, b_shard), ()

    cache_shapes = jax.eval_shape(
        lambda: T.init_cache(cfg, shape.global_batch, shape.seq_len))
    c_shard = cache_sharding(cache_shapes, rules, nb)
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def serve_step(params, cache, batch, pos):
        return T.decode_step(params, cfg, cache, batch, pos)

    return (serve_step, (p_shapes, cache_shapes, specs, pos),
            (p_shard, c_shard, b_shard, NamedSharding(rules.mesh, P())), (1,))


# ---------------------------------------------------------------------------
# HLO analysis
# ---------------------------------------------------------------------------

def parse_collectives(hlo_text: str, body_mult: int = 1) -> dict:
    """Sum RESULT-shape bytes of every collective op in the partitioned
    HLO (operands are printed without types). Shapes are per-device; the
    ring model converts to wire bytes per device.

    HLO cost counting sees while bodies once; collectives inside non-ENTRY
    computations (the layer-scan bodies) are scaled by `body_mult` (the
    layer trip count). ENTRY-level collectives (embed/loss/optimizer)
    count once."""
    per_op: dict[str, float] = {}
    wire_per_dev = 0.0
    n_ops = 0
    shape_re = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
    mult = 1
    for line in hlo_text.splitlines():
        if line.rstrip().endswith("{") and not line.startswith(" "):
            mult = 1 if line.lstrip().startswith("ENTRY") else body_mult
        m = re.search(r"= (\([^)]*\)|[^ ]+) ([a-z-]+)\(", line)
        if not m:
            continue
        result_ty, op = m.group(1), m.group(2)
        base = op.removesuffix("-start")
        if base not in COLLECTIVES or op.endswith("-done"):
            continue
        nbytes = 0
        for dt, dims in shape_re.findall(result_ty):
            if dt not in DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES[dt]
        gm = re.search(r"replica_groups=\{\{([0-9, ]+)\}", line)
        gsize = len(gm.group(1).split(",")) if gm else 1
        gm2 = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
        if gm2:
            gsize = int(gm2.group(2))
        n_ops += 1
        nbytes *= mult
        per_op[base] = per_op.get(base, 0.0) + nbytes
        # ring-model wire bytes per device (result-shape based)
        if base == "all-reduce":
            wire_per_dev += 2 * nbytes * (gsize - 1) / max(gsize, 1)
        elif base == "all-gather":
            wire_per_dev += nbytes * (gsize - 1) / max(gsize, 1)
        elif base in ("reduce-scatter", "all-to-all"):
            wire_per_dev += nbytes * (gsize - 1) / max(gsize, 1)
        else:                                   # collective-permute
            wire_per_dev += nbytes
    return {"per_op_bytes": per_op, "n_collectives": n_ops,
            "operand_bytes_total": sum(per_op.values()),
            "wire_bytes_per_device": wire_per_dev}


def inner_scan_flop_correction(cfg, shape: ShapeSpec) -> float:
    """Closed-form FLOPs executed by inner-scan bodies beyond HLO cost
    analysis's body-once counting (chunked attention / SSD chunk scans).

    The cost pass unrolls the LAYER scan, so per layer exactly one inner
    body is already counted; the correction adds the remaining
    (trips - 1) bodies. Train multiplies by 4 (fwd + remat recompute +
    ~2x grad). Exact for dot-product bodies (attention, SSD einsums).
    """
    import math
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        return 0.0
    prefix = cfg.n_prefix_tokens if cfg.family == "vlm" else 0
    s_eff = s + prefix
    mult = 4.0 if shape.kind == "train" else 1.0
    total = 0.0
    h, dh = cfg.n_heads, cfg.d_head
    if cfg.family in ("dense", "moe", "vlm", "encdec") and s_eff > 2048:
        qc, kc = math.gcd(s_eff, 512), math.gcd(s_eff, 1024)
        nq, nk = s_eff // qc, s_eff // kc
        body = 4.0 * b * h * qc * kc * dh
        n_attn = cfg.n_layers
        if cfg.family == "encdec":
            n_attn += cfg.n_enc_layers + cfg.n_layers    # enc self + cross
        total += n_attn * (nq * nk - 1) * body * mult
    if cfg.family == "hybrid" and s_eff > 2048:
        qc = math.gcd(s_eff, 512)
        nq = s_eff // qc
        body = 4.0 * b * h * qc * (cfg.window_size + qc) * dh
        total += cfg._layer_kinds().count("attn") * (nq - 1) * body * mult
    if cfg.family == "ssm":
        q = min(128, s_eff)
        nc = s_eff // q
        d_in = cfg.ssm_expand * cfg.d_model
        nh = d_in // cfg.ssm_head_dim
        p_, n_ = cfg.ssm_head_dim, cfg.ssm_state
        body = b * (2.0 * q * q * n_ + nh * q * q + 2.0 * nh * q * q * p_
                    + 4.0 * q * nh * p_ * n_)
        total += cfg.n_layers * (nc - 1) * body * mult
    return total


def attn_model_flops(cfg, shape: ShapeSpec) -> float:
    """Ideal attention FLOPs for MODEL_FLOPS (6ND misses the S^2 term)."""
    b, s = shape.global_batch, shape.seq_len
    hdh = cfg.n_heads * cfg.d_head
    kinds = cfg._layer_kinds()
    n_attn = kinds.count("attn")
    if cfg.family == "encdec":
        n_attn = cfg.n_layers + cfg.n_enc_layers
    if n_attn == 0:
        return 0.0
    if shape.kind == "decode":
        kv = min(s, cfg.window_size) if cfg.family == "hybrid" else s
        return 4.0 * b * kv * hdh * n_attn
    kv_per_q = min(s, cfg.window_size) if cfg.family == "hybrid" else s * 0.5
    base = 4.0 * b * s * kv_per_q * hdh * n_attn
    return base * (3.0 if shape.kind == "train" else 1.0)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: str = RESULTS_DIR, variant: str = "") -> dict:
    cfg = load_arch(arch)
    if "bf16gather" in variant:
        cfg = dataclasses.replace(cfg, bf16_param_gather=True)
    if "int8kv" in variant:
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    shape = SHAPES[shape_name]
    ok, why = cell_is_applicable(cfg, shape)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell = f"{arch}__{shape_name}__{mesh_name}"
    if variant:
        cell += f"__{variant}"
    result: dict = {"cell": cell, "arch": arch, "shape": shape_name,
                    "mesh": mesh_name, "applicable": ok}
    if not ok:
        result["skip_reason"] = why
        _write(out_dir, cell, result)
        return result

    if "tp8" in variant:
        # elastic re-mesh: same 256/512 chips factorized (data=32, model=8)
        # so model divides 40 q-heads and 8 kv-heads evenly
        shape_ = (2, 32, 8) if multi_pod else (32, 8)
        axes_ = ("pod", "data", "model") if multi_pod else ("data", "model")
        mesh = auto_mesh(shape_, axes_)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    # ZeRO-3 only where there is optimizer state to shard; serving keeps
    # weights TP-sharded without per-layer regathers
    rules = ShardRules(mesh, fsdp=(shape.kind == "train"),
                       seq_axis="model" if "sp" in variant else None,
                       fsdp_layer_dim=("fsdpL" in variant))
    t0 = time.time()
    with rules_scope(rules):
        step_fn, arg_shapes, shardings, donate = build_cell(cfg, shape, rules)
        jitted = jax.jit(step_fn, in_shardings=shardings,
                         donate_argnums=donate)
        lowered = jitted.lower(*arg_shapes)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        # ---- global cost pass: unrolled layer scan, lowered only --------
        # HLO cost analysis counts while bodies ONCE; unrolling the layer
        # scan (trip count 1) makes it count every layer. Inner chunk
        # scans are corrected in closed form (exact dot-product bodies).
        t1 = time.time()
        cfg_u = dataclasses.replace(cfg, scan_unroll=max(cfg.n_layers, 1))
        fn_u, args_u, shard_u, don_u = build_cell(cfg_u, shape, rules)
        lowered_u = jax.jit(fn_u, in_shardings=shard_u,
                            donate_argnums=don_u).lower(*args_u)
        cost_u = lowered_u.cost_analysis()
        t_cost = time.time() - t1

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    cost_l = lowered.cost_analysis()
    n_dev = mesh.devices.size

    corr = inner_scan_flop_correction(cfg, shape)
    flops_global = (cost_u.get("flops") or 0.0) + corr
    # trip-ratio R scales the fused per-device bytes for loop trips
    rolled_flops_global = max(cost_l.get("flops") or 1.0, 1.0)
    r_trip = max(flops_global / rolled_flops_global, 1.0)
    bytes_dev = (cost.get("bytes accessed") or 0.0) * r_trip
    trips = max(cfg.n_layers // max(len(cfg.block_pattern), 1)
                if cfg.family == "hybrid" else cfg.n_layers, 1)
    coll_scaled = parse_collectives(compiled.as_text(), body_mult=trips)
    coll_scaled["wire_bytes_per_device_scaled"] = \
        coll_scaled.pop("wire_bytes_per_device")
    coll_once = parse_collectives(compiled.as_text(), body_mult=1)
    coll_scaled["wire_bytes_per_device"] = coll_once["wire_bytes_per_device"]

    result.update({
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "cost_pass_s": round(t_cost, 1),
        "n_devices": n_dev,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "cost": {"flops": cost.get("flops"),
                 "bytes_accessed": cost.get("bytes_accessed") or
                 cost.get("bytes accessed"),
                 "flops_global": flops_global,
                 "flops_unrolled_lowered": cost_u.get("flops"),
                 "inner_scan_correction": corr,
                 "trip_ratio": r_trip,
                 "bytes_per_device_scaled": bytes_dev},
        "collectives": coll_scaled,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "attn_model_flops": attn_model_flops(cfg, shape),
        "shape": dataclasses.asdict(shape),
    })
    _write(out_dir, cell, result)
    return result


def _write(out_dir: str, cell: str, result: dict) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, cell + ".json"), "w") as f:
        json.dump(result, f, indent=1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", default="",
                    help="optimization variant suffix (e.g. bf16gather)")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in SHAPES:
                for mp in ((False, True) if args.mesh == "both" else
                           ((args.mesh == "multi"),)):
                    cells.append((a, s, mp))
    else:
        assert args.arch and args.shape
        for mp in ((False, True) if args.mesh == "both" else
                   ((args.mesh == "multi"),)):
            cells.append((args.arch, args.shape, mp))

    for a, s, mp in cells:
        mesh_name = "pod2x16x16" if mp else "pod16x16"
        suffix = f"__{args.variant}" if args.variant else ""
        path = os.path.join(args.out, f"{a}__{s}__{mesh_name}{suffix}.json")
        if args.skip_existing and os.path.exists(path):
            with open(path) as f:
                if "error" not in json.load(f):
                    print(f"[skip] {a} {s} {mesh_name}")
                    continue
        print(f"[cell] {a} {s} {mesh_name} ...", flush=True)
        try:
            r = run_cell(a, s, mp, args.out, args.variant)
            status = "SKIP " + r.get("skip_reason", "") if not r["applicable"] \
                else (f"ok compile={r['compile_s']}s "
                      f"flops={r['cost']['flops']:.3g} "
                      f"peak={r['memory']['peak_bytes']}")
            print(f"       {status}", flush=True)
        except Exception as e:                                   # noqa: BLE001
            print(f"       FAIL {type(e).__name__}: {e}", flush=True)
            _write(args.out, f"{a}__{s}__{mesh_name}{suffix}",
                   {"cell": f"{a}__{s}__{mesh_name}{suffix}", "error": str(e)})


if __name__ == "__main__":
    main()
