import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the real step function (train_step with optimizer,
or serve prefill/decode step), lowers it with ShapeDtypeStruct stand-ins (no
allocation), compiles it against the production mesh, and records:

  * ``memory_analysis()``  — per-device bytes: proves the cell fits,
  * ``cost_analysis()``    — per-device HLO FLOPs / bytes accessed,
  * collective bytes       — parsed from the *compiled* (post-SPMD) HLO:
    per-device operand bytes of all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute ops,
  * the three roofline terms (see benchmarks/roofline.py for constants).

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]

Results are written incrementally to results/dryrun/<cell>.json so long runs
resume for free.
"""

import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES_BY_NAME, applicable, get_config
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh, serve_rules, train_rules
from repro.models import Model
from repro.parallel import sharding as shlib
from repro.parallel.axes import shard_ctx
from repro.train.optimizer import AdamW
from repro.train.train_loop import make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1, "c64": 8}

_COLL_RE = re.compile(
    r"=\s+(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^)]*\))?\s*->")
_CALL_RE = re.compile(r"(?:body|to_apply|calls|condition)=%?([\w\.\-]+)")
_WHILE_RE = re.compile(
    r"while\(.*?body=%?([\w\.\-]+).*?condition=%?([\w\.\-]+)|"
    r"while\(.*?condition=%?([\w\.\-]+).*?body=%?([\w\.\-]+)", re.S)
_CONST_RE = re.compile(r"constant\((\d+)\)")


def input_specs(arch: str, shape_name: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell.

    train/prefill: the token batch; decode: the single-step token batch
    (the KV cache is part of the step signature and built separately)."""
    del arch  # shapes are arch-independent for the LM family
    return input_specs_of(SHAPES_BY_NAME[shape_name])


def _shape_bytes(type_str: str) -> float:
    """Bytes of an HLO type string; for tuples, the largest element (async
    collective tuples repeat operand+result)."""
    best = 0.0
    for dm in _SHAPE_RE.finditer(type_str):
        dt, dims = dm.group(1), dm.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        best = max(best, n * _DTYPE_BYTES[dt])
    return best


# ring-algorithm per-device traffic relative to the op's result bytes:
# all-reduce moves ~2× its tensor (reduce-scatter + all-gather phases).
_KIND_WEIGHT = {"all-gather": 1.0, "all-reduce": 2.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}


def _collective_bytes(hlo_text: str) -> tuple[float, dict]:
    """Per-device collective traffic from compiled (post-SPMD) HLO.

    Collectives inside ``while`` bodies (lax.scan over layers, engine loops)
    execute trip-count times but print once, so we account per computation
    and multiply along the call graph: bytes(comp) = own + Σ bytes(callee)
    × trip(callee).  Trip counts come from the largest integer literal in
    the while condition computation (exact for counted loops, which is all
    this framework emits).
    """
    # split into computations (they start at column 0 with '%name (' / ENTRY)
    comps: dict[str, list[str]] = {}
    current = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            m = _COMP_RE.match(line.strip())
            if m:
                current = m.group(1)
                comps[current] = []
                continue
        if current is not None:
            comps[current].append(line)

    own: dict[str, dict[str, float]] = {}
    calls: dict[str, list[tuple[str, str]]] = {}  # comp -> [(callee, role)]
    trip: dict[str, int] = {}
    for name, lines in comps.items():
        own[name] = {}
        calls[name] = []
        for line in lines:
            cm = _COLL_RE.search(line)
            if cm:
                kind = cm.group(2)
                own[name][kind] = own[name].get(kind, 0.0) \
                    + _shape_bytes(cm.group(1))
            if " while(" in line:
                wm = _WHILE_RE.search(line)
                if wm:
                    body = wm.group(1) or wm.group(4)
                    cond = wm.group(2) or wm.group(3)
                    calls[name].append((body, "while"))
                    consts = [int(c) for c in _CONST_RE.findall(
                        "\n".join(comps.get(cond, [])))]
                    trip[body] = max(consts) if consts else 1
            else:
                for callee in _CALL_RE.findall(line):
                    calls[name].append((callee, "call"))

    memo: dict[str, dict[str, float]] = {}

    def total_of(name: str, depth=0) -> dict[str, float]:
        if name in memo or depth > 50:
            return memo.get(name, {})
        acc = dict(own.get(name, {}))
        memo[name] = {}  # cycle guard
        for callee, role in calls.get(name, ()):
            sub = total_of(callee, depth + 1)
            mult = trip.get(callee, 1) if role == "while" else 1
            for k, v in sub.items():
                acc[k] = acc.get(k, 0.0) + v * mult
        memo[name] = acc
        return acc

    entry = next((n for n in comps if "main" in n), None)
    per_kind = total_of(entry) if entry else {}
    total = sum(v * _KIND_WEIGHT.get(k, 1.0) for k, v in per_kind.items())
    return total, per_kind


def _compile_variant(cfg, shape, mesh, rules, grad_accum: int = 1) -> tuple:
    """Lower + compile one step-fn variant; returns (compiled, timings)."""
    t0 = time.monotonic()
    is_train = shape.kind == "train"
    model = Model(cfg)
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
    if cfg.family == "audio":
        init = lambda k: model.init(k, max_dec_len=shape.seq_len)  # noqa
    else:
        init = model.init
    params_sds = jax.eval_shape(init, key_sds)
    p_spec = shlib.param_specs(params_sds, mesh, rules)
    p_shard = shlib.to_shardings(p_spec, mesh)
    batch_sds = input_specs_of(shape)
    b_shard = shlib.to_shardings(
        shlib.batch_specs(batch_sds, mesh, rules), mesh)
    with shard_ctx(mesh, rules):
        if is_train:
            opt = AdamW()
            opt_sds = jax.eval_shape(opt.init, params_sds)
            # optimizer moments shard exactly like their params
            o_spec = {"m": p_spec, "v": p_spec,
                      "step": jax.sharding.PartitionSpec()}
            o_shard = shlib.to_shardings(o_spec, mesh)
            step = make_train_step(model, opt, grad_accum=grad_accum)
            lowered = jax.jit(
                step,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None),
                donate_argnums=(0, 1),
            ).lower(params_sds, opt_sds, batch_sds)
        elif shape.kind == "prefill":
            def prefill_step(params, batch):
                logits, _ = model.forward(params, batch["tokens"])
                return logits

            lowered = jax.jit(
                prefill_step, in_shardings=(p_shard, b_shard),
            ).lower(params_sds, batch_sds)
        else:  # decode
            cache_sds = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            c_shard = shlib.to_shardings(
                shlib.cache_specs(cache_sds, mesh, rules), mesh)

            def serve_step(params, cache, batch):
                logits, new_cache = model.decode_step(
                    params, cache, batch["tokens"])
                nxt = jnp.argmax(logits[:, -1, :], axis=-1)
                return nxt, new_cache

            lowered = jax.jit(
                serve_step,
                in_shardings=(p_shard, c_shard, b_shard),
                out_shardings=(None, c_shard),
                donate_argnums=(1,),
            ).lower(params_sds, cache_sds, batch_sds)
        t_lower = time.monotonic() - t0
        compiled = lowered.compile()
        t_compile = time.monotonic() - t0 - t_lower
    return compiled, (t_lower, t_compile)


def input_specs_of(shape) -> dict:
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    if shape.kind == "train":
        return {"tokens": jax.ShapeDtypeStruct((B, S), i32),
                "labels": jax.ShapeDtypeStruct((B, S), i32)}
    if shape.kind == "prefill":
        return {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
    return {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}


def _with_layers(cfg, L: int):
    """Structure-preserving layer-count reduction for cost probes."""
    kw = {"n_layers": L}
    if cfg.encdec is not None:
        kw["encdec"] = dataclasses.replace(cfg.encdec, n_enc_layers=L)
    return dataclasses.replace(cfg, **kw)


def _probe_plan(cfg) -> tuple[list[int], list, list[float]]:
    """Probe layer counts + per-point feature rows + full-config feature.

    Costs are affine in the feature vector: (1, n_layers[, n_shared]) —
    exact because segments are homogeneous.  Returns (Ls, rows, full_row).
    """
    from repro.models.transformer import structure
    L = cfg.n_layers
    if cfg.family == "hybrid":
        p = cfg.hybrid.period

        def nsh(n):
            c = dataclasses.replace(cfg, n_layers=n)
            return sum(1 for k, _ in structure(c) if k == "shared_attn")

        Ls = [p, 2 * p, p + 2]
        rows = [[1.0, n, float(nsh(n))] for n in Ls]
        return Ls, rows, [1.0, float(L), float(nsh(L))]
    if L <= 8:  # small enough to unroll fully — no extrapolation
        return [L], [[1.0]], [1.0]
    Ls = [2, 4]
    rows = [[1.0, float(n)] for n in Ls]
    return Ls, rows, [1.0, float(L)]


def _cost_probe(cfg, shape, mesh, rules, grad_accum: int = 1) -> dict:
    """Exact cost accounting via unrolled reduced-depth probes +
    linear extrapolation in layer count."""
    import numpy as np

    Ls, rows, full_row = _probe_plan(cfg)
    metrics = []
    for L in Ls:
        c = _with_layers(dataclasses.replace(cfg, scan_layers=False), L)
        compiled, _ = _compile_variant(c, shape, mesh, rules,
                                       grad_accum=grad_accum)
        ca = compiled.cost_analysis()
        coll, kinds = _collective_bytes(compiled.as_text())
        metrics.append({"flops": float(ca.get("flops", 0.0)),
                        "bytes": float(ca.get("bytes accessed", 0.0)),
                        "coll": coll,
                        **{f"coll_{k}": v for k, v in kinds.items()}})
    keys = sorted({k for m in metrics for k in m})
    A = np.asarray(rows)
    out = {}
    for k in keys:
        y = np.asarray([m.get(k, 0.0) for m in metrics])
        coef, *_ = np.linalg.lstsq(A, y, rcond=None)
        out[k] = float(max(np.asarray(full_row) @ coef, 0.0))
    out["probe_layers"] = Ls
    return out


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               verbose: bool = True, with_costs: bool = True,
               cfg_override=None, rules_override=None,
               grad_accum: int = 1) -> dict:
    """Lower+compile one cell; returns the analysis record.

    Two compilations: (a) the deployable scan-over-layers form — proves the
    cell compiles on the mesh and gives the memory analysis; (b) unrolled
    reduced-depth probes for exact flops/bytes/collective accounting
    (XLA cost analysis counts while bodies once, hence the probes).
    """
    shape = SHAPES_BY_NAME[shape_name]
    cfg = cfg_override or get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    is_train = shape.kind == "train"
    if not is_train:  # serving: bf16 params, no optimizer
        cfg = dataclasses.replace(cfg, param_dtype="bfloat16", remat="none")
    if rules_override is not None:
        rules = rules_override
    else:
        rules = (train_rules(cfg.seq_shard, fsdp=cfg.fsdp)
                 if is_train else serve_rules())

    compiled, (t_lower, t_compile) = _compile_variant(
        cfg, shape, mesh, rules, grad_accum=grad_accum)
    ma = compiled.memory_analysis()
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(mesh.devices.size),
        "kind": shape.kind,
        "ok": True,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "mem": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "code_bytes": int(ma.generated_code_size_in_bytes),
        },
    }
    del compiled
    if with_costs:
        costs = _cost_probe(cfg, shape, mesh, rules, grad_accum=grad_accum)
        rec.update({
            "flops_per_dev": costs["flops"],
            "bytes_per_dev": costs["bytes"],
            "coll_bytes_per_dev": costs["coll"],
            "coll_kinds": {k[5:]: v for k, v in costs.items()
                           if k.startswith("coll_")},
            "probe_layers": costs["probe_layers"],
        })
    if verbose:
        msg = (f"[dryrun] {arch} × {shape_name} × {rec['mesh']}: "
               f"mem(arg+tmp)="
               f"{(rec['mem']['argument_bytes'] + rec['mem']['temp_bytes'])/2**30:.2f}GiB "
               f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
        if with_costs:
            msg += (f" flops/dev={rec['flops_per_dev']:.3e} "
                    f"bytes/dev={rec['bytes_per_dev']:.3e} "
                    f"coll/dev={rec['coll_bytes_per_dev']:.3e}")
        print(msg, flush=True)
    return rec


def run_all(mesh_mode: str = "both", only_arch: Optional[str] = None,
            only_shape: Optional[str] = None, force: bool = False):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    modes = {"single": [False], "multi": [True],
             "both": [False, True]}[mesh_mode]
    for arch, cfg in ARCHS.items():
        if only_arch and arch != only_arch:
            continue
        for shape_name in SHAPES_BY_NAME:
            if only_shape and shape_name != only_shape:
                continue
            ok, why = applicable(cfg, SHAPES_BY_NAME[shape_name])
            for multi in modes:
                cell = f"{arch}__{shape_name}__{'multi' if multi else 'single'}"
                out = os.path.join(RESULTS_DIR, cell + ".json")
                if os.path.exists(out) and not force:
                    print(f"[dryrun] skip {cell} (done)")
                    continue
                if not ok:
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": "2x16x16" if multi else "16x16",
                           "ok": False, "skipped": True, "reason": why}
                else:
                    try:
                        # multi-pod: compile proof only (roofline is 16x16)
                        rec = lower_cell(arch, shape_name, multi_pod=multi,
                                         with_costs=not multi)
                    except Exception as e:  # noqa: BLE001
                        rec = {"arch": arch, "shape": shape_name,
                               "mesh": "2x16x16" if multi else "16x16",
                               "ok": False, "error": repr(e),
                               "trace": traceback.format_exc()[-2000:]}
                        print(f"[dryrun] FAIL {cell}: {e!r}")
                with open(out, "w") as f:
                    json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=sorted(ARCHS))
    ap.add_argument("--shape", default=None, choices=sorted(SHAPES_BY_NAME))
    ap.add_argument("--mesh", default="both",
                    choices=("single", "multi", "both"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()
    if args.all or args.arch is None:
        run_all(args.mesh, only_arch=args.arch, only_shape=args.shape,
                force=args.force)
    else:
        for multi in {"single": [False], "multi": [True],
                      "both": [False, True]}[args.mesh]:
            lower_cell(args.arch, args.shape or "train_4k", multi_pod=multi)


if __name__ == "__main__":
    main()
