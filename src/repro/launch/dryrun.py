"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces, WITHOUT allocating model memory
(ShapeDtypeStruct inputs only):

* proof the distribution config is coherent (`.lower().compile()`),
* ``memory_analysis()``  — per-device bytes (fits-in-HBM evidence),
* ``cost_analysis()``    — FLOPs / bytes for §Roofline,
* HLO collective-bytes breakdown (§Roofline collective term).

One cell per process (XLA leaks across big compiles on one core):
``python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
--mesh single`` runs one cell; ``--all`` spawns subprocesses.

Results land in ``experiments/dryrun/<mesh>/<arch>__<shape>.json``.
"""

import argparse
import json
import os
import subprocess
import sys
import time
import traceback


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             collectives: str = "xla", remat: str = "dots",
             variant: str = "baseline", num_chains: int | str = 1,
             ar_algo: str = "rs_ag", compress_grads: bool = False,
             bucket_bytes: int | None = None,
             topology: str | None = None,
             src_read_bw: int | None = None,
             overlap: bool = False) -> dict:
    import jax

    from repro import configs as C
    from repro.launch import roofline as R
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_cell

    ok, reason = C.applicable(arch, shape_name)
    rec: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "collectives": collectives, "remat": remat, "variant": variant,
        "num_chains": num_chains, "ar_algo": ar_algo,
        "compress_grads": compress_grads, "bucket_bytes": bucket_bytes,
        "topology": topology, "src_read_bw": src_read_bw,
    }
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    cell = build_cell(arch, shape_name, mesh, collectives=collectives,
                      num_chains=num_chains, ar_algo=ar_algo,
                      remat=remat, variant=variant,
                      compress_grads=compress_grads,
                      bucket_bytes=bucket_bytes,
                      topology=topology)
    rec["num_chains"] = cell.num_chains  # effective K (VARIANTS resolved)
    rec["ar_algo"] = cell.ar_algo
    rec["compress_grads"] = cell.compress_grads
    rec["bucket_bytes"] = cell.bucket_bytes
    rec["topology"] = cell.topology
    lowered = cell.lower()
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    mem = compiled.memory_analysis()
    roof = R.extract(compiled)
    cfg = cell.cfg
    n_active = _active_params(arch, cfg)
    tokens = (
        cell.shape.global_batch * cell.shape.seq_len
        if cell.shape.kind in ("train", "prefill")
        else cell.shape.global_batch
    )
    mf = R.model_flops(n_active, tokens, cell.shape.kind)
    chips = mesh.devices.size
    flops_global = roof.flops * chips

    rec.update(
        status="ok",
        lower_s=round(t1 - t0, 2),
        compile_s=round(t2 - t1, 2),
        chips=chips,
        memory_analysis=_mem_dict(mem),
        roofline=roof.as_dict(),
        model_flops_global=mf,
        hlo_flops_global=flops_global,
        useful_flops_ratio=(mf / flops_global) if flops_global else None,
    )
    if overlap:
        # Modeled bucketed-overlap timeline + HLO async/interleaving
        # evidence.  Prices the inner "data"-axis ring stage (the only
        # stage on single-pod meshes, where the model is exact).
        from repro.launch.hlo_breakdown import overlap_stats
        from repro.models import transformer as T

        leaves = jax.tree.leaves(
            jax.eval_shape(lambda: T.model_init(jax.random.PRNGKey(0), cfg))
        )
        bb = cell.bucket_bytes or (4 << 20)
        rec["overlap_model"] = R.modeled_train_overlap(
            leaves,
            int(mesh.shape["data"]),
            max(1, tokens // chips),
            bucket_bytes=bb,
            num_chains=cell.num_chains,
            algo=cell.ar_algo,
            wire_dtype="int8" if cell.compress_grads else None,
            topology=cell.topology,
            src_read_bw=src_read_bw,
        )
        rec["hlo_overlap"] = overlap_stats(compiled.as_text())
    return rec


def _active_params(arch: str, cfg) -> int:
    """Active (per-token) parameter count — MoE counts top-k+shared."""
    import jax

    from repro.models import transformer as T

    shapes = jax.eval_shape(lambda: T.model_init(jax.random.PRNGKey(0), cfg))
    total = sum(x.size for x in jax.tree.leaves(shapes))
    if not cfg.num_experts:
        return total
    # subtract routed-expert params not active per token
    moe_layers = sum(
        1 for i in range(cfg.num_layers) if cfg.layer_spec(i).ffn == "moe"
    )
    per_expert = 3 * cfg.d_model * cfg.moe_d_ff
    inactive = moe_layers * per_expert * (cfg.num_experts - cfg.moe_top_k)
    return total - inactive


def _mem_dict(mem) -> dict:
    out = {}
    for key in (
        "temp_size_in_bytes",
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
        "peak_memory_in_bytes",
    ):
        val = getattr(mem, key, None)
        if val is not None:
            out[key] = int(val)
    if not out:
        out["repr"] = str(mem)
    return out


def main() -> None:
    # CLI-only: fake a 512-device host platform BEFORE the jax backend
    # initializes (set here, not at import, so importing this module for
    # _cell_suffix etc. never changes the caller's device count; --all
    # workers re-run main() in their own process and set it themselves)
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")
    )
    p = argparse.ArgumentParser()
    p.add_argument("--arch")
    p.add_argument("--shape")
    p.add_argument("--mesh", choices=("single", "multi"), default="single")
    p.add_argument("--collectives", choices=("xla", "torrent"), default="xla")
    p.add_argument("--remat", default="dots")
    p.add_argument("--variant", default="baseline",
                   help="optimization bundle from steps.VARIANTS")
    p.add_argument("--num-chains", type=_parse_num_chains, default=1,
                   help="multi-chain Chainwrite sub-rings per DP "
                        "reduction (with --collectives torrent), or "
                        "'auto' to pick K from the all_reduce_latency "
                        "model; sweepable next to --collectives")
    from repro.core.chainwrite_ref import ALL_REDUCE_ALGOS  # numpy-only

    p.add_argument("--ar-algo", choices=ALL_REDUCE_ALGOS,
                   default="rs_ag",
                   help="multi-ring all-reduce schedule: fused "
                        "reduce-scatter/all-gather (bandwidth-optimal "
                        "default) or full-payload rotation")
    p.add_argument("--compress-grads", action="store_true", default=False,
                   help="int8 wire for the DP gradient all-reduce "
                        "(requires --collectives torrent)")
    p.add_argument("--bucket-mb", type=float, default=None,
                   help="bucket size (MiB) for the bucketed, backward-"
                        "overlapped DP grad reduce (requires "
                        "--collectives torrent)")
    p.add_argument("--topology", default=None,
                   help="tiered link-graph spec for auto-K ring planning "
                        "(requires --collectives torrent), e.g. "
                        "'pods=4x(4x4):interpod_bw=0.25' or 'pods=2'; "
                        "parsed by core.topology.parse_topology_spec")
    p.add_argument("--src-read-bw", type=int, default=None,
                   help="source HBM read bandwidth (bytes/cc) for the "
                        "modeled overlap timeline; None = unconstrained "
                        "(link-bw-limited)")
    p.add_argument("--overlap", action="store_true", default=False,
                   help="emit the modeled bucketed-overlap timeline "
                        "(roofline.modeled_train_overlap) and HLO "
                        "async/interleaving counts in the record")
    p.add_argument("--out", default="experiments/dryrun")
    p.add_argument("--all", action="store_true")
    p.add_argument("--meshes", default="single,multi")
    p.add_argument("--timeout", type=int, default=3000)
    args = p.parse_args()

    if args.all:
        from repro import configs as C

        failures = []
        for mesh_kind in args.meshes.split(","):
            for arch in C.ARCHS:
                for shape in C.SHAPES:
                    rc = _run_subprocess(arch, shape, mesh_kind, args)
                    if rc != 0:
                        failures.append((mesh_kind, arch, shape))
        if failures:
            print("FAILED CELLS:", failures)
            sys.exit(1)
        print("ALL CELLS OK")
        return

    out_dir = os.path.join(args.out, args.mesh)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(
        out_dir, f"{args.arch}__{args.shape}{_cell_suffix(args)}.json"
    )
    try:
        rec = run_cell(
            args.arch, args.shape, args.mesh, out_dir,
            collectives=args.collectives, remat=args.remat,
            variant=args.variant, num_chains=args.num_chains,
            ar_algo=args.ar_algo, compress_grads=args.compress_grads,
            bucket_bytes=(
                int(args.bucket_mb * (1 << 20)) if args.bucket_mb else None
            ),
            topology=args.topology,
            src_read_bw=args.src_read_bw,
            overlap=args.overlap,
        )
    except Exception:
        rec = {
            "arch": args.arch, "shape": args.shape, "mesh": args.mesh,
            "status": "error", "traceback": traceback.format_exc(),
        }
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        print(rec["traceback"], file=sys.stderr)
        sys.exit(1)
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    if rec["status"] == "ok":
        print(
            f"{args.arch} × {args.shape} × {args.mesh}: OK "
            f"compile={rec['compile_s']}s dominant={rec['roofline']['dominant']}"
        )
    else:
        print(f"{args.arch} × {args.shape} × {args.mesh}: {rec['status']} ({rec.get('reason','')})")


def _parse_num_chains(value: str):
    """CLI type for --num-chains: a positive int or the literal 'auto'."""
    if value == "auto":
        return "auto"
    k = int(value)
    if k < 1:
        raise argparse.ArgumentTypeError("num-chains must be >= 1 or 'auto'")
    return k


def _cell_suffix(args) -> str:
    """Output-file suffix encoding every non-default cell knob — shared
    by the single-cell writer and the --all cache check so sweeps over
    different knobs never collide on (or get skipped for) one path."""
    suffix = "" if args.collectives == "xla" else f"__{args.collectives}"
    if args.num_chains != 1:
        suffix += f"__k{args.num_chains}"
    if args.ar_algo != "rs_ag":
        suffix += f"__{args.ar_algo}"
    if args.compress_grads:
        suffix += "__int8"
    mb = getattr(args, "bucket_mb", 0)
    if mb:
        suffix += f"__b{int(mb) if mb == int(mb) else mb}MB"
    topo = getattr(args, "topology", None)
    if topo:
        # spec strings contain ':'/'('/')' — sanitize for filenames
        safe = "".join(c if c.isalnum() or c in "x=." else "-" for c in topo)
        suffix += f"__topo-{safe}"
    srbw = getattr(args, "src_read_bw", None)
    if srbw:
        suffix += f"__srbw{srbw}"
    if args.variant != "baseline":
        suffix += f"__{args.variant}"
    if args.remat != "dots":
        suffix += f"__remat-{args.remat}"
    return suffix


def _run_subprocess(arch: str, shape: str, mesh_kind: str, args) -> int:
    out_dir = os.path.join(args.out, mesh_kind)
    path = os.path.join(out_dir, f"{arch}__{shape}{_cell_suffix(args)}.json")
    if os.path.exists(path):
        with open(path) as f:
            if json.load(f).get("status") in ("ok", "skipped"):
                print(f"skip (cached): {path}")
                return 0
    cmd = [
        sys.executable, "-m", "repro.launch.dryrun",
        "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
        "--collectives", args.collectives, "--remat", args.remat,
        "--num-chains", str(args.num_chains), "--ar-algo", args.ar_algo,
        "--variant", args.variant, "--out", args.out,
    ]
    if args.compress_grads:
        cmd.append("--compress-grads")
    if args.bucket_mb:
        cmd += ["--bucket-mb", str(args.bucket_mb)]
    if getattr(args, "topology", None):
        cmd += ["--topology", args.topology]
    if getattr(args, "src_read_bw", None):
        cmd += ["--src-read-bw", str(args.src_read_bw)]
    if args.overlap:
        cmd.append("--overlap")
    print("::", " ".join(cmd[3:]), flush=True)
    try:
        # the dry-run compiles for virtual CPU devices; never the chip
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        r = subprocess.run(cmd, timeout=args.timeout, env=env)
        return r.returncode
    except subprocess.TimeoutExpired:
        print(f"TIMEOUT: {arch} {shape} {mesh_kind}")
        return 124


if __name__ == "__main__":
    main()
