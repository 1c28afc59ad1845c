import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: 512 host
placeholder devices stand in for 2 TPU v5e pods; ``jax.jit(...).lower()``
+ ``.compile()`` must succeed, and the compiled artifact yields

  * ``memory_analysis()``  — per-device bytes (does it fit 16 GB/chip?)
  * ``cost_analysis()``    — HLO FLOPs / bytes for the roofline terms
  * collective bytes       — parsed from the optimized HLO text
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute operand sizes)

Artifacts are cached as JSON per cell under --out (1-core container:
compiles are the long pole; re-runs skip completed cells).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b \
      --shape train_4k --mesh single            # one cell
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]
  PYTHONPATH=src python -m repro.launch.dryrun --arch smoke       # CI cell

``--arch smoke`` lowers+compiles a reduced (smoke-variant) config on a tiny
train shape — the CI-sized proof that the whole lower/compile/artifact
pipeline works, in seconds instead of hours.
"""

import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED, get_config
from repro.configs.base import ArchConfig, InputShape, SHAPES_BY_NAME
from repro.models import model_zoo as Z

# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins — weak-type-correct, no allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ArchConfig, shape: InputShape) -> dict:
    """Abstract inputs for one cell. Training: the data batch; serving:
    the request batch (prompt tokens or decode tokens)."""
    b, s = shape.global_batch, shape.seq_len
    specs = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    if cfg.encoder is not None:
        d_in = cfg.encoder.d_input or cfg.d_model
        specs["frontend"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder.n_positions, d_in), jnp.float32
        )
    return specs


def skip_reason(cfg: ArchConfig, shape: InputShape) -> str | None:
    """Documented skips (DESIGN.md §5)."""
    if shape.name == "long_500k" and not cfg.is_sub_quadratic:
        return "long_500k requires sub-quadratic attention (DESIGN.md §5)"
    if shape.kind == "decode" and not cfg.has_decoder:
        return "encoder-only arch has no decode step"
    return None


# ---------------------------------------------------------------------------
# collective-byte accounting from optimized HLO
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}
_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)
_SHAPE_RE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op, by kind.

    HLO lines look like:
      ``%ar = f32[1024,512]{1,0} all-reduce(%x), replica_groups=...``
    The result shape of a collective equals (or bounds) the moved payload
    per device; we also record op counts.
    """
    out = {k: {"bytes": 0, "count": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        for kind in _COLLECTIVES:
            if f" {kind}(" in stripped or f"{kind}-start(" in stripped:
                lhs = stripped.split("=", 1)
                shape_part = lhs[1] if len(lhs) == 2 else stripped
                shape_part = shape_part.split(kind)[0]
                out[kind]["bytes"] += _shape_bytes(shape_part)
                out[kind]["count"] += 1
                break
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items() if isinstance(v, dict))
    return out


# ---------------------------------------------------------------------------
# scan-body correction
# ---------------------------------------------------------------------------
#
# XLA's cost_analysis reports PER-DEVICE numbers and counts a while/scan body
# ONCE (verified empirically: a scan of 8 matmuls reports 1 matmul of flops).
# Our stacks lower the repeating period as one lax.scan over n_periods, so a
# cell's raw numbers undercount by (n_periods - 1) x (one period body).  We
# lower the period body separately under the same mesh/shardings and publish
#   corrected = raw + (n_periods - 1) * body
# for flops, bytes and collective bytes.  (Residual scan-once undercount:
# the SSD inter-chunk state scan's tiny state-passing einsums — documented.)


def lower_period_body(cfg: ArchConfig, shape: InputShape, mesh) -> dict:
    """Per-device cost of ONE period iteration for this cell's step kind."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import transformer as T
    from repro.runtime import sharding as SH

    if not cfg.n_periods:
        return {"flops": 0.0, "bytes_accessed": 0.0, "collectives": 0, "n_periods": 0}

    b, s = shape.global_batch, shape.seq_len
    s_eff = 1 if shape.kind == "decode" else s
    serving = shape.kind != "train"

    def one_period(pslice, x, positions, caches):
        aux = jnp.float32(0.0)
        new_caches = []
        mode = "serve" if serving else "train"
        for j, kind in enumerate(cfg.pattern_period):
            cj = caches[j] if caches is not None else None
            x, cj, a = T.block_apply(pslice[j], x, cfg, kind, mode, positions, cj)
            aux += a
            new_caches.append(cj if cj is not None else 0)
        return x, aux, new_caches

    def build_pslice(key):
        ks = jax.random.split(key, len(cfg.pattern_period))
        blocks = [
            T.init_block(ks[j], cfg, kind) for j, kind in enumerate(cfg.pattern_period)
        ]
        if serving:
            blocks = [Z.prepare_serving_params(b_, cfg) for b_ in blocks]
        return blocks

    pslice = jax.eval_shape(build_pslice, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((b, s_eff, cfg.d_model), jnp.bfloat16)
    positions = jax.ShapeDtypeStruct((b, s_eff), jnp.int32)
    caches = None
    if shape.kind in ("prefill", "decode"):
        caches = jax.eval_shape(
            lambda: [
                T.init_block_cache(b, s, cfg, kind) for kind in cfg.pattern_period
            ]
        )

    if shape.kind == "train":
        def fn(pslice, x, positions):
            def scalar(ps, xx):
                y, aux, _ = one_period(ps, xx, positions, None)
                return jnp.sum(y.astype(jnp.float32)) + aux

            return jax.grad(scalar, argnums=(0, 1))(pslice, x)
        args = (pslice, x, positions)
    else:
        def fn(pslice, x, positions, caches):
            return one_period(pslice, x, positions, caches)
        args = (pslice, x, positions, caches)

    p_sh = SH.params_shardings(pslice, mesh, fsdp=not serving)
    x_sh = NamedSharding(
        mesh, P(*(list(SH.logical_batch_spec(b, s_eff, mesh)) + [None]))
    )
    pos_sh = NamedSharding(mesh, SH.logical_batch_spec(b, s_eff, mesh))
    in_sh = (p_sh, x_sh, pos_sh) + ((SH.cache_shardings(caches, mesh, b),) if caches is not None else ())
    with mesh:
        compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops") or 0.0),
        "bytes_accessed": float(cost.get("bytes accessed") or 0.0),
        "collectives": coll,
        "n_periods": cfg.n_periods,
    }


# ---------------------------------------------------------------------------
# cell runners
# ---------------------------------------------------------------------------


def _abstract_params(cfg: ArchConfig, serving: bool):
    def build(key):
        return Z.init_serving_params(key, cfg) if serving else Z.init_params(key, cfg)

    return jax.eval_shape(build, jax.random.PRNGKey(0))


def lower_cell(cfg: ArchConfig, shape: InputShape, mesh, accum_steps: int = 1):
    """Build + lower the step function for one cell. Returns (lowered, meta)."""
    from repro.optim import adamw
    from repro.runtime import serve_loop, sharding as SH, train_loop

    specs = input_specs(cfg, shape)
    b, s = shape.global_batch, shape.seq_len

    if shape.kind == "train":
        tcfg = train_loop.TrainConfig(
            optimizer=adamw.AdamWConfig(), accum_steps=accum_steps
        )
        step = train_loop.make_train_step(cfg, tcfg, mesh, specs)
        params = _abstract_params(cfg, serving=False)
        opt = jax.eval_shape(lambda p: adamw.init_state(p), params)
        with mesh:
            lowered = step.lower(params, opt, specs)
        return lowered, {"step": "train_step", "accum": accum_steps}

    params = _abstract_params(cfg, serving=True)
    if shape.kind == "prefill":
        fn = serve_loop.make_prefill(cfg, mesh, b, s, s)
        cache = jax.eval_shape(lambda: Z.init_cache(b, s, cfg))
        args = (params, specs["tokens"], cache)
        if "frontend" in specs:
            args = args + (specs["frontend"],)
        with mesh:
            lowered = fn.lower(*args)
        return lowered, {"step": "prefill"}

    # decode: one new token against a cache of seq_len
    fn = serve_loop.make_decode_step(cfg, mesh, b, s)
    cache = jax.eval_shape(lambda: Z.init_cache(b, s, cfg))
    tok = jax.ShapeDtypeStruct((b,), jnp.int32)
    with mesh:
        lowered = fn.lower(params, tok, cache)
    return lowered, {"step": "decode_step"}


OPT_TRANSFORMS = {
    # §Perf hillclimb knobs — each is one hypothesis->change iteration
    "scores_bf16": dict(attn_scores_dtype="bf16"),
    "logits_bf16": dict(logits_dtype="bf16"),
    "gqa_expand": dict(gqa_mode="expand"),
    "packed_gather": "quant",  # binarize+pack before the FSDP all-gather
}


def apply_opts(cfg: ArchConfig, opts) -> ArchConfig:
    import dataclasses as _dc

    for o in opts or ():
        if o.startswith("accum"):
            continue  # handled by accum_steps
        if o == "packed_gather":
            cfg = _dc.replace(
                cfg, quant=_dc.replace(cfg.quant, prebinarize_gather=True)
            )
            continue
        cfg = _dc.replace(cfg, **OPT_TRANSFORMS[o])
    return cfg


def run_cell(
    arch: str,
    shape_name: str,
    mesh_kind: str,
    out_dir: str,
    accum_steps: int = 1,
    compile_: bool = True,
    opts=(),
) -> dict:
    from repro.launch.mesh import make_production_mesh

    if arch == "smoke":
        from repro.configs.smoke import smoke_variant

        cfg = apply_opts(smoke_variant(get_config("granite-8b")), opts)
    else:
        cfg = apply_opts(get_config(arch), opts)
    shape = SMOKE_SHAPE if shape_name == SMOKE_SHAPE.name else SHAPES_BY_NAME[shape_name]
    record: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "kind": shape.kind,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "opts": list(opts or ()),
        "time": time.time(),
    }
    reason = skip_reason(cfg, shape)
    if reason:
        record.update(status="skip", reason=reason)
        return record

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    record["mesh_shape"] = dict(mesh.shape)
    try:
        t0 = time.time()
        lowered, meta = lower_cell(cfg, shape, mesh, accum_steps)
        record.update(meta)
        record["lower_s"] = round(time.time() - t0, 1)
        if not compile_:
            record["status"] = "lowered"
            return record
        t0 = time.time()
        compiled = lowered.compile()
        record["compile_s"] = round(time.time() - t0, 1)

        mem = compiled.memory_analysis()
        for field in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
            "alias_size_in_bytes",
        ):
            record.setdefault("memory", {})[field] = getattr(mem, field, None)

        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        record["cost"] = {
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
            "transcendentals": cost.get("transcendentals"),
        }
        hlo = compiled.as_text()
        record["collectives"] = collective_bytes(hlo)
        record["hlo_lines"] = hlo.count("\n")
        # scan-body correction (see module comment): one extra small lowering
        t0 = time.time()
        try:
            record["period_body"] = lower_period_body(cfg, shape, mesh)
        except Exception as e:  # noqa: BLE001
            record["period_body"] = {"error": f"{type(e).__name__}: {e}"}
        record["body_lower_s"] = round(time.time() - t0, 1)
        record["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — recorded, sweep continues
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-4000:]
    return record


def cell_path(out_dir: str, arch: str, shape: str, mesh_kind: str, suffix: str = "") -> str:
    tail = f"__{suffix}" if suffix else ""
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh_kind}{tail}.json")


#: The CI cell: reduced config, reduced shape — lower+compile in seconds.
SMOKE_SHAPE = InputShape("smoke", 128, 8, "train")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(ASSIGNED) + ["smoke"], default=None)
    ap.add_argument(
        "--shape", choices=list(SHAPES_BY_NAME) + [SMOKE_SHAPE.name], default=None
    )
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true", help="sweep every cell")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--accum-steps", type=int, default=1)
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    ap.add_argument("--no-compile", action="store_true", help="lower only")
    ap.add_argument("--opt", action="append", default=[], choices=list(OPT_TRANSFORMS))
    ap.add_argument("--suffix", default="", help="artifact suffix for variants")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    archs = list(ASSIGNED) if (args.all or args.arch is None) else [args.arch]
    if args.arch == "smoke" and args.shape is None and not args.all:
        shapes = [SMOKE_SHAPE.name]
    else:
        shapes = list(SHAPES_BY_NAME) if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                path = cell_path(args.out, arch, shape, mesh_kind, args.suffix)
                if os.path.exists(path) and not args.force:
                    with open(path) as f:
                        prev = json.load(f)
                    if prev.get("status") in ("ok", "skip"):
                        print(f"[cached] {arch} {shape} {mesh_kind}: {prev['status']}")
                        continue
                print(f"[run] {arch} {shape} {mesh_kind} ...", flush=True)
                rec = run_cell(
                    arch, shape, mesh_kind, args.out, args.accum_steps,
                    compile_=not args.no_compile, opts=args.opt,
                )
                with open(path, "w") as f:
                    json.dump(rec, f, indent=2)
                msg = rec["status"]
                if rec["status"] == "ok":
                    msg += (
                        f" flops={rec['cost']['flops']:.3e}"
                        f" coll={rec['collectives']['total_bytes']:.3e}B"
                        f" lower={rec['lower_s']}s compile={rec['compile_s']}s"
                    )
                elif rec["status"] == "error":
                    msg += f" ({rec['error'][:200]})"
                print(f"[done] {arch} {shape} {mesh_kind}: {msg}", flush=True)


if __name__ == "__main__":
    main()
