import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count="
    + os.environ.get("REPRO_DRYRUN_DEVICES", "512")
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) on
the production meshes, print memory/cost analyses, and emit roofline terms.

The two lines above MUST stay the first statements in this module (jax locks
the device count at first init). Do not import this module from tests —
run it as a script / subprocess:

    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --json out.json
"""
import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from typing import Optional  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_IDS, MODEL_CONFIGS, get_shape, SHAPES  # noqa: E402
from repro.launch.mesh import make_production_mesh, num_chips  # noqa: E402
from repro.launch.roofline import analyze  # noqa: E402
from repro.launch.specs import input_specs, params_specs, skip_reason, state_specs  # noqa: E402
from repro.models.params import count_params_analytic  # noqa: E402
from repro.sharding.rules import (  # noqa: E402
    cache_pspecs,
    input_pspecs,
    opt_state_pspecs,
    param_pspecs,
)
from repro.train.train_step import make_prefill_step, make_serve_step, make_train_step  # noqa: E402


def _named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def model_flops_estimate(cfg, shape) -> float:
    """6*N*D for train (fwd+bwd), 2*N_active*D for inference steps."""
    n_active = count_params_analytic(cfg, active_only=True)
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch * 1  # decode: one token


def lower_combo(arch: str, shape_name: str, mesh, mesh_name: str,
                verbose: bool = True, unroll: bool = False) -> dict:
    """Two-phase dry-run for one combo:

    1. scan-layers compile  -> proves lowering + per-device memory fit
       (deployment form: O(1)-in-depth HLO).
    2. (optional, --unroll) unrolled compile -> exact HloCostAnalysis FLOPs /
       bytes / collective-bytes (XLA counts while-loop bodies once, so the
       scan form under-reports; see EXPERIMENTS §Roofline methodology).
    """
    import dataclasses

    from repro.sharding.ctx import mesh_context, unroll_context

    cfg = MODEL_CONFIGS[arch]
    shape = get_shape(shape_name)
    reason = skip_reason(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skip", "reason": reason}

    specs = input_specs(cfg, shape)

    with mesh_context(mesh):
        out = _lower_inner(cfg, arch, shape, shape_name, mesh, mesh_name,
                           specs, time.time(), verbose)
        if unroll:
            try:
                cost = _depth_probe_cost(cfg, arch, shape, shape_name, mesh,
                                         mesh_name)
                out.update(cost)
                if verbose:
                    print(f"    [depth-probe cost] "
                          f"t_comp={out['t_compute']*1e3:.2f}ms "
                          f"t_mem={out['t_memory']*1e3:.2f}ms "
                          f"t_coll={out['t_collective']*1e3:.2f}ms "
                          f"bottleneck={out['bottleneck']} "
                          f"useful={out['useful_flops_ratio']:.3f}")
            except Exception:  # noqa: BLE001
                traceback.print_exc()
                out["cost_source"] = "scan-underestimate"
        return out


def _depth_probe_cost(cfg, arch, shape, shape_name, mesh, mesh_name) -> dict:
    """Exact-cost extrapolation: HloCostAnalysis counts loop bodies once, so
    instead of unrolling the full depth (intractable compiles), lower two
    shallow fully-unrolled variants. Per-layer/unit cost is exactly linear,
    so  cost(L) = a + b*L  recovers the full-depth FLOPs / bytes /
    collective-bytes. Hybrid archs use one vs two 6-layer periods as the
    unit; MoE archs keep their dense prefix in `a`."""
    from repro.launch.roofline import analyze
    from repro.sharding.ctx import unroll_context

    prefix = cfg.first_dense_layers if cfg.moe.enabled else 0
    if cfg.arch_type == "hybrid" and cfg.hybrid is not None:
        k = cfg.hybrid.attn_every
        l1, l2 = k, 2 * k
        n_units, rem_frac = divmod(cfg.num_layers, k)
        rem_frac = rem_frac / k  # remainder ssm layers ~ fraction of a period
    else:
        l1, l2 = prefix + 1, prefix + 2
        n_units, rem_frac = cfg.num_layers - prefix, 0.0

    def probe(layers):
        c = dataclasses.replace(
            cfg, num_layers=layers, scan_layers=False, microbatch=1,
        )
        specs_p = input_specs(c, get_shape(shape_name))
        with unroll_context(True):
            r = _lower_inner(c, arch, shape, shape_name, mesh, mesh_name,
                             specs_p, time.time(), False)
        return r

    t0 = time.time()
    r1 = probe(l1)
    r2 = probe(l2)
    units1 = (1 if cfg.arch_type == "hybrid" else l1 - prefix)
    units2 = (2 if cfg.arch_type == "hybrid" else l2 - prefix)

    def extrap(key):
        b = (r2[key] - r1[key]) / (units2 - units1)
        a = r1[key] - b * units1
        return max(a + b * (n_units + rem_frac), 0.0)

    flops = extrap("flops")
    hbm = extrap("hbm_bytes")
    coll = extrap("collective_bytes")
    colls = {
        kk: max(
            r1["collectives"][kk]
            + (r2["collectives"][kk] - r1["collectives"][kk])
            / (units2 - units1) * (n_units + rem_frac - units1),
            0,
        )
        for kk in r1["collectives"]
    }
    from repro.launch.mesh import HBM_BW, ICI_BW_PER_LINK, PEAK_FLOPS_BF16

    chips = num_chips(mesh)
    t_comp = flops / (chips * PEAK_FLOPS_BF16)
    t_mem = hbm / HBM_BW
    t_coll = coll / ICI_BW_PER_LINK
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    mf = model_flops_estimate(cfg, get_shape(shape_name))
    return {
        "flops": flops, "hbm_bytes": hbm, "collective_bytes": coll,
        "collectives": colls, "t_compute": t_comp, "t_memory": t_mem,
        "t_collective": t_coll, "bottleneck": max(terms, key=terms.get),
        "useful_flops_ratio": mf / flops if flops else 0.0,
        # allow[bench-timing]: times a lowering depth probe — host-synchronous; no device work to block on
        "cost_source": "depth-probe", "cost_compile_s": time.time() - t0,
    }


def _lower_inner(cfg, arch, shape, shape_name, mesh, mesh_name, specs, t0,
                 verbose):
    if shape.kind == "train":
        fn = make_train_step(cfg)
        state_sds = state_specs(cfg)
        pspec = param_pspecs(cfg, state_sds["params"], mesh)
        ospec = opt_state_pspecs(cfg, state_sds["opt"], pspec, mesh)
        st_shard = {"params": _named(mesh, pspec), "opt": _named(mesh, ospec),
                    "step": NamedSharding(mesh, P())}
        b_shard = _named(mesh, input_pspecs(cfg, specs["batch"], mesh))
        jitted = jax.jit(fn, in_shardings=(st_shard, b_shard), donate_argnums=0)
        lowered = jitted.lower(state_sds, specs["batch"])
    elif shape.kind == "prefill":
        fn = make_prefill_step(cfg)
        p_sds = params_specs(cfg)
        pspec = param_pspecs(cfg, p_sds, mesh)
        b_shard = _named(mesh, input_pspecs(cfg, specs["batch"], mesh))
        jitted = jax.jit(fn, in_shardings=(_named(mesh, pspec), b_shard))
        lowered = jitted.lower(p_sds, specs["batch"])
    else:  # decode
        long_mode = shape_name == "long_500k"
        fn = make_serve_step(cfg, long_mode=long_mode)
        p_sds = params_specs(cfg)
        pspec = param_pspecs(cfg, p_sds, mesh)
        c_shard = _named(mesh, cache_pspecs(cfg, specs["cache"], mesh))
        t_shard = _named(mesh, input_pspecs(cfg, {"tokens": specs["tokens"]}, mesh))["tokens"]
        jitted = jax.jit(
            fn,
            in_shardings=(_named(mesh, pspec), c_shard, NamedSharding(mesh, P()), t_shard),
            donate_argnums=1,
        )
        lowered = jitted.lower(p_sds, specs["cache"], specs["cache_index"], specs["tokens"])

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    # allow[bench-timing]: times lower()/compile() — host-synchronous; no device work to block on
    t_compile = time.time() - t0

    roof = analyze(
        compiled, arch=arch, shape=shape_name, mesh_name=mesh_name,
        chips=num_chips(mesh), model_flops=model_flops_estimate(cfg, shape),
    )
    mem = compiled.memory_analysis()
    if verbose:
        print(f"--- {arch} x {shape_name} x {mesh_name} "
              f"(lower {t_lower:.1f}s, compile {t_compile:.1f}s)")
        print(f"    memory_analysis: {mem}")
        ca = compiled.cost_analysis()
        print(f"    cost_analysis: flops={ca.get('flops', 0):.3e} "
              f"bytes={ca.get('bytes accessed', 0):.3e}")
        print(f"    roofline: t_comp={roof.t_compute*1e3:.2f}ms "
              f"t_mem={roof.t_memory*1e3:.2f}ms t_coll={roof.t_collective*1e3:.2f}ms "
              f"bottleneck={roof.bottleneck} useful={roof.useful_flops_ratio:.2f}")
    out = roof.to_dict()
    out.update(status="ok", lower_s=t_lower, compile_s=t_compile,
               memory_analysis=str(mem))
    return out


def lower_glm(name: str, mesh, mesh_name: str, verbose: bool = True) -> dict:
    """Dry-run the paper's own workload: one distributed d-GLMNET outer
    iteration (subproblem + AllReduce + line search) at Table-2 scale.

    epsilon/dna lower densely; glm-webspam (dense X would be 10.5 TB) uses
    the by-feature sparse step (paper Table-1 layout, DESIGN §2.3). The
    step programs come from the ``repro.api`` strategy resolver
    (``mesh_programs``) — the same resolution live solves get.
    """
    from repro.api import mesh_programs
    from repro.configs.glm import GLM_CONFIGS
    from repro.core.dglmnet import DGLMNETOptions
    from repro.launch.roofline import analyze

    cfg = GLM_CONFIGS[name]
    mdim = mesh.shape["model"]
    tile = 128
    n = cfg.num_examples
    ddim = num_chips(mesh) // mdim
    n -= n % ddim
    p = ((cfg.num_features + mdim * tile - 1) // (mdim * tile)) * (mdim * tile)

    opts = DGLMNETOptions(tile=tile, method="gram")
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)
    t0 = time.time()
    if name == "glm-webspam":
        # by-feature sparse layout (paper Table 1): dense X would be 10.5 TB.
        # K = padded nnz per feature per data shard (avg 72/16 -> 64 covers
        # the tail with the sentinel mechanism).
        k_pad = 64
        step, _ = mesh_programs(mesh, opts, layout="slab")
        lowered = jax.jit(step).lower(
            sds((p, ddim, k_pad), jnp.int32), sds((p, ddim, k_pad), jnp.float32),
            sds((n,), jnp.float32), sds((p,), jnp.float32),
            sds((n,), jnp.float32), sds((), jnp.float32),
        )
    else:
        step, _ = mesh_programs(mesh, opts, layout="dense")
        lowered = jax.jit(step).lower(
            sds((n, p), jnp.float32), sds((n,), jnp.float32),
            sds((p,), jnp.float32), sds((n,), jnp.float32),
            sds((), jnp.float32),
        )
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    # allow[bench-timing]: times lower()/compile() — host-synchronous; no device work to block on
    t_compile = time.time() - t0
    # model flops: one outer iteration = Gram tiles + margins ~ 2*n*p*(tile+2)
    mf = 2.0 * n * p * (tile + 2)
    roof = analyze(compiled, arch=name, shape="dglmnet_step",
                   mesh_name=mesh_name, chips=num_chips(mesh), model_flops=mf)
    mem = compiled.memory_analysis()
    if verbose:
        print(f"--- {name} x dglmnet_step x {mesh_name} "
              f"(lower {t_lower:.1f}s, compile {t_compile:.1f}s)")
        print(f"    memory_analysis: {mem}")
        print(f"    roofline: t_comp={roof.t_compute*1e3:.2f}ms "
              f"t_mem={roof.t_memory*1e3:.2f}ms "
              f"t_coll={roof.t_collective*1e3:.2f}ms "
              f"bottleneck={roof.bottleneck}")
    out = roof.to_dict()
    out.update(status="ok", lower_s=t_lower, compile_s=t_compile,
               memory_analysis=str(mem))
    return out


def lower_glm_screened(mesh, mesh_name: str, verbose: bool = True) -> list:
    """Lowering-only dry-run of the *screened distributed path*'s moving
    parts at Table-2 dims on the production mesh (ROADMAP "production mesh
    scale"): proves the 16x16 lowering of

    * the sparse strong-rule screen (``core.screening.make_sparse_screen``
      slab stream, psum over data axes) at webspam shape;
    * the by-feature sparse subproblem step over slabs with the *blocked*
      semi-parallel CD cycle (slab_gram/slab_spmv suite +
      ``cd_cycle_blocked_tile``);
    * the dense subproblem step with the Pallas ``blocked_cd`` kernel
      (epsilon shape, ``use_kernel=True``).

    No ``.compile()`` and no execution — ``.lower()`` alone certifies the
    shard_map programs partition at mesh scale; compile cost for the full
    p=16.6M scan is the production TPU's business, not CI's. All programs
    come from ``repro.api.mesh_programs`` — the strategy resolver the live
    solves use.
    """
    from repro.api import mesh_programs
    from repro.configs.glm import GLM_CONFIGS
    from repro.core.dglmnet import DGLMNETOptions

    mdim = mesh.shape["model"]
    ddim = num_chips(mesh) // mdim
    tile = 128
    opts = DGLMNETOptions(tile=tile, method="gram", cycle_mode="blocked",
                          block=16)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)
    results = []

    def record(label, fn, *args):
        t0 = time.time()
        fn(*args)          # .lower() inside; any failure propagates
        out = {"arch": label, "shape": "screened_path", "mesh": mesh_name,
               # allow[bench-timing]: times .lower() only — host-synchronous; no device work to block on
               "status": "ok", "lower_s": time.time() - t0}
        if verbose:
            print(f"--- {label} x screened_path x {mesh_name} "
                  f"(lower {out['lower_s']:.1f}s, lowering-only)")
        results.append(out)

    # webspam: sparse screen + sparse blocked step over (p, DP, K) slabs
    cfg = GLM_CONFIGS["glm-webspam"]
    n = cfg.num_examples - cfg.num_examples % ddim
    n_loc = n // ddim
    p = ((cfg.num_features + mdim * tile - 1) // (mdim * tile)) * (mdim * tile)
    k_pad = 64
    slab_i = sds((p, ddim, k_pad), jnp.int32)
    slab_f = sds((p, ddim, k_pad), jnp.float32)
    vec_n = sds((n,), jnp.float32)
    step_sparse, screen = mesh_programs(mesh, opts, layout="slab",
                                        n_loc=n_loc)
    record("glm-webspam-screen",
           lambda: screen.lower(slab_i, slab_f, vec_n, vec_n))
    record("glm-webspam-blocked-step",
           lambda: jax.jit(step_sparse).lower(
               slab_i, slab_f, vec_n, sds((p,), jnp.float32), vec_n,
               sds((), jnp.float32)))

    # epsilon: dense step with the Pallas blocked_cd kernel on the mesh
    cfg = GLM_CONFIGS["glm-epsilon"]
    n = cfg.num_examples - cfg.num_examples % ddim
    p = ((cfg.num_features + mdim * tile - 1) // (mdim * tile)) * (mdim * tile)
    step_dense, _ = mesh_programs(
        mesh, DGLMNETOptions(tile=tile, cycle_mode="blocked", block=16,
                             use_kernel=True), layout="dense")
    record("glm-epsilon-blocked-kernel-step",
           lambda: jax.jit(step_dense).lower(
               sds((n, p), jnp.float32), sds((n,), jnp.float32),
               sds((p,), jnp.float32), sds((n,), jnp.float32),
               sds((), jnp.float32)))
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="input shape (default: all)")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both", "dev"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--json", default=None, help="write results JSON here")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll layer loops for exact cost_analysis")
    ap.add_argument("--glm", action="store_true",
                    help="also dry-run the paper's GLM workload (Table-2 dims)")
    ap.add_argument("--glm-screened", action="store_true",
                    help="lowering-only dry-run of the screened distributed "
                         "path (sparse screen + blocked-cycle steps) at "
                         "Table-2 dims")
    ap.add_argument("--flash-decode", action="store_true",
                    help="seq-parallel flash-decode attention (hillclimb)")
    args = ap.parse_args()
    if args.flash_decode:
        import contextlib

        from repro.sharding.ctx import flash_decode_context

        _stack = contextlib.ExitStack()
        _stack.enter_context(flash_decode_context(True))

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    if args.mesh == "dev":
        from repro.launch.mesh import make_dev_mesh

        mesh_list = [(make_dev_mesh(), "2x4-dev")]
    else:
        multis = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
        mesh_list = [
            (make_production_mesh(multi_pod=m), "2x16x16" if m else "16x16")
            for m in multis
        ]

    results = []
    for mesh, mesh_name in mesh_list:
        if args.glm:
            from repro.configs.glm import GLM_CONFIGS

            for gname in GLM_CONFIGS:
                try:
                    results.append(lower_glm(gname, mesh, mesh_name))
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    results.append({"arch": gname, "shape": "dglmnet_step",
                                    "mesh": mesh_name, "status": "error",
                                    "error": repr(e)})
        if args.glm_screened:
            try:
                results.extend(lower_glm_screened(mesh, mesh_name))
            except Exception as e:  # noqa: BLE001
                traceback.print_exc()
                results.append({"arch": "glm-screened",
                                "shape": "screened_path", "mesh": mesh_name,
                                "status": "error", "error": repr(e)})
        if (args.glm or args.glm_screened) and args.arch is None \
                and not args.all:
            continue
        for arch in archs:
            for shape in shapes:
                try:
                    results.append(
                        lower_combo(arch, shape, mesh, mesh_name, unroll=args.unroll)
                    )
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    results.append({"arch": arch, "shape": shape,
                                    "mesh": mesh_name, "status": "error",
                                    "error": repr(e)})
    ok = sum(r["status"] == "ok" for r in results)
    skip = sum(r["status"] == "skip" for r in results)
    err = sum(r["status"] == "error" for r in results)
    print(f"\n=== dry-run: {ok} ok, {skip} skip, {err} error ===")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    raise SystemExit(1 if err else 0)


if __name__ == "__main__":
    main()
