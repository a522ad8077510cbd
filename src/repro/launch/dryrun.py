import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST run before any other import — jax locks the
device count at first init.  Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b \
        --shape train_4k [--multi-pod] [--out artifacts/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --sweep

Per cell this produces: compiled.memory_analysis(), cost_analysis(),
and collective-bytes parsed from the optimized HLO — the §Roofline inputs.
"""
import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import cachedirs  # noqa: E402

from repro.configs import ARCHS, get_config  # noqa: E402
from repro.configs.base import SHAPES, cell_status  # noqa: E402
from repro.dist import sharding as shd  # noqa: E402
from repro.launch import specs as S  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models.model import build  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.train.train_step import TrainConfig, TrainState, make_train_step  # noqa: E402

cachedirs.enable_compile_cache()

_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^=]*=\s*\(?([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-operand bytes of every collective op in optimized HLO."""
    out = {}
    for m in _COLL_RE.finditer(hlo_text):
        op, dt, dims = m.group(1), m.group(2), m.group(3)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[op] = out.get(op, 0) + n * _DTYPE_BYTES[dt]
    out["total"] = sum(v for k, v in out.items())
    return out


def build_step(arch: str, shape: str, mesh):
    cfg = get_config(arch)
    sc = SHAPES[shape]
    model = build(cfg, constrain=shd.make_constrain(mesh))
    specs = S.input_specs(model, cfg, sc, mesh)
    # decode placement: tp2d for batch==1 (§Perf B2), pure-TP for batched
    # decode (§Perf A5); train/prefill keep FSDP×TP
    pmode = "fsdp"
    if sc.kind == "decode" and os.environ.get("REPRO_DECODE_TP2D", "1") == "1" \
            and sc.global_batch == 1:
        pmode = "tp2d"  # pure-TP ('tp') for batched decode was REFUTED:
        #                 −10.7% coll but +17.6% bytes and 26 GB/dev temps
        #                 (> v5e HBM) on qwen3-32b — §Perf A5
    pspecs = S.param_specs(model, cfg, mesh, pmode)

    if sc.kind == "train":
        tcfg = TrainConfig()
        step = make_train_step(model, tcfg)
        # optimizer moments shard like their params
        mu = jax.tree.map(lambda p: jax.ShapeDtypeStruct(
            p.shape, jnp.float32, sharding=p.sharding), pspecs)
        state_specs = TrainState(
            params=pspecs,
            opt=adamw.OptState(step=jax.ShapeDtypeStruct((), jnp.int32),
                               mu=mu, nu=mu))

        def fn(state, batch):
            return step(state, batch)

        args = (state_specs, specs["batch"])
        donate = (0,)
    elif sc.kind == "prefill":
        model_local = model

        def fn(params, batch):
            return model_local.prefill(params, batch)

        args = (pspecs, specs["batch"])
        donate = ()
    else:  # decode
        def fn(params, cache, tokens, pos):
            return model.decode_step(params, cache, tokens, pos)

        args = (pspecs, specs["cache"], specs["tokens"], specs["pos"])
        donate = (1,)
        # pin the updated cache to its input sharding — otherwise GSPMD
        # may materialize a replicated cache on the way out (§Perf A2)
        out_shardings = (None,
                         jax.tree.map(lambda s: s.sharding, specs["cache"]))
        return fn, args, donate, out_shardings

    return fn, args, donate, None


def run_cell(arch: str, shape: str, multi_pod: bool, outdir: str):
    status = cell_status(arch, shape)
    meshname = "2x16x16" if multi_pod else "16x16"
    tag = f"{arch}__{shape}__{meshname}"
    os.makedirs(outdir, exist_ok=True)
    path = os.path.join(outdir, tag + ".json")
    if status != "run":
        rec = {"arch": arch, "shape": shape, "mesh": meshname,
               "status": status}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        print(f"[dryrun] {tag}: {status}")
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    with mesh:
        fn, args, donate, out_sh = build_step(arch, shape, mesh)
        kw = {"out_shardings": out_sh} if out_sh is not None else {}
        lowered = jax.jit(fn, donate_argnums=donate, **kw).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_bytes(hlo)

    rec = {
        "arch": arch, "shape": shape, "mesh": meshname, "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops": cost.get("flops", 0.0),
        "bytes_accessed": cost.get("bytes accessed", 0.0),
        "collective_bytes": coll,
        "memory": {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_size_bytes":
                getattr(mem, "generated_code_size_in_bytes", 0),
        },
    }
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[dryrun] {tag}: ok  lower={t_lower:.0f}s compile={t_compile:.0f}s"
          f" flops={rec['flops']:.3g} coll={coll['total']:.3g}B")
    print("  memory_analysis:", rec["memory"])
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--sweep", action="store_true",
                    help="all (arch × shape) cells on this mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    cells = []
    if args.sweep:
        for a in ARCHS:
            for s in SHAPES:
                cells.append((a, s))
    else:
        cells.append((args.arch, args.shape))

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = []
    for mp in meshes:
        for a, s in cells:
            try:
                run_cell(a, s, mp, args.out)
            except Exception as e:  # noqa: BLE001
                failures.append((a, s, mp, repr(e)))
                traceback.print_exc()
    if failures:
        print("FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("dry-run complete: all cells ok")


if __name__ == "__main__":
    main()
