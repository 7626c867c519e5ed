"""Cross-layout checkpoint resharding: elastic resume across mesh changes.

PR 5's resilience runtime resumes bit-identically — onto the SAME
layout. On a preemptible fleet that is half the problem: losing a host
invalidates the ICI mesh, the elastic relaunch lands on a different
chip count, and the planner (`paddle_tpu.planner.plan`) hands the
survivor a different dp/fsdp/tp/pp factorization. This module carries
the training state across that layout change (the Pathways-style
resharded resume; reference lineage: the fleet elastic manager's
checkpoint-restart protocol, `fleet/elastic/manager.py`):

- `reshard_restore(ckpt_dir, step, target_layout, mesh)` loads a PR-5
  manifest checkpoint saved under layout A into a model living under
  ANY planner layout B — smaller or larger world, different axes —
  leaf by leaf with the TARGET `Sharding` attached to each restore
  (orbax reads only the shards each host needs: no full-model host
  materialization on any single host), covering optimizer slots and
  the `core/random` RNG key exactly like a same-layout resume;
- the manifest is cross-checked first (per-leaf shape/dtype, per-file
  sha256), a corrupt file is still reported as a corrupt LEAF, and
  `step=None` keeps `CheckpointManager.restore`'s newest -> oldest
  fallback semantics (an explicit step raises instead);
- checkpoints record the layout they were saved under
  (`RunState.layout`), so `ResilienceManager.resume()` can route
  through this module automatically when the stored layout mismatches
  the live one — the relaunched process never needs to know whether
  the world changed.

The restore deliberately places parameters on their TAG-derived
shardings (`env.param_sharding`); ZeRO re-placement (stage-3 dp
sharding of params/states) stays where it always happened — in
`ShardedTrainStep.__init__` — so the reshard path has exactly one
placement rule instead of a second copy of the trainer's.
"""
import os
import warnings

import numpy as np

from .. import monitor
from .ckpt import (CheckpointError, CheckpointManager, load_manifest)

__all__ = ["reshard_restore", "normalize_layout", "layout_from_mesh",
           "layouts_differ", "stored_layout"]

MESH_AXES = ("dp", "pp", "mp", "sp", "ep")


# ---------------------------------------------------------------------------
# layout identity
# ---------------------------------------------------------------------------

def normalize_layout(layout):
    """Canonical layout dict from a planner `Layout`, a dict, or None.

    The canonical form carries every mesh axis (missing axes are 1) and
    `zero_stage` when the source declares one — enough to decide
    whether two runs share a placement, nothing more."""
    if layout is None:
        return None
    if hasattr(layout, "to_dict"):          # planner.Layout
        layout = layout.to_dict()
    if not isinstance(layout, dict):
        raise TypeError(
            f"layout must be a planner Layout or an axis dict, got "
            f"{type(layout).__name__}")
    out = {}
    for a in MESH_AXES:
        v = int(layout.get(a, 1))
        if v < 1:
            raise ValueError(f"layout axis {a} size {v} < 1")
        out[a] = v
    if layout.get("zero_stage") is not None:
        out["zero_stage"] = int(layout["zero_stage"])
    return out


def layout_from_mesh(mesh):
    """The live mesh's layout dict (axes absent from the mesh are 1)."""
    if mesh is None:
        return None
    out = {}
    for a in MESH_AXES:
        out[a] = int(mesh.shape[a]) if a in mesh.axis_names else 1
    return out


def layouts_differ(a, b):
    """Do two layouts place state differently? Mesh axes always count;
    zero_stage counts only when BOTH sides declare one (a mesh-derived
    layout carries no stage and must not spuriously mismatch)."""
    a, b = normalize_layout(a), normalize_layout(b)
    if a is None or b is None:
        return False
    if any(a[ax] != b[ax] for ax in MESH_AXES):
        return True
    if "zero_stage" in a and "zero_stage" in b and \
            a["zero_stage"] != b["zero_stage"]:
        return True
    return False


def stored_layout(manager, step=None):
    """The layout stamped into a committed checkpoint's RunState (the
    newest committed step by default), or None when no checkpoint —
    or no stamp (a pre-elastic checkpoint) — exists. Reads only
    run_state.json; integrity verification happens at restore time."""
    import json
    from .ckpt import RUN_STATE_NAME
    if step is None:
        step = manager.latest_step()
    if step is None:
        return None
    path = os.path.join(manager.step_dir(step), RUN_STATE_NAME)
    try:
        with open(path) as f:
            d = json.load(f)
    except (OSError, ValueError):
        return None
    layout = d.get("layout")
    return normalize_layout(layout) if layout else None


# ---------------------------------------------------------------------------
# the resharding leaf loader
# ---------------------------------------------------------------------------

def _flat_leaves(tree, prefix=""):
    """Dotted-name -> live leaf for a `_state_pytree` tree, joining
    keys exactly like `ckpt.flatten_leaves` so names line up with the
    manifest's leaf table."""
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flat_leaves(v, prefix=name + "."))
        else:
            out[name] = v
    return out


def _restore_structure(ckptr, path, saved):
    """The checkpoint's own tree structure, each leaf holding its
    dotted name. Primary source: orbax `metadata()` — it preserves
    EMPTY subtrees (a stateless-SGD run saves `"optimizer": {}`, and
    a restore_args tree missing that key is a structure mismatch
    orbax rejects outright). Fallback: reconstruction from the
    manifest's leaf names (which cannot represent empty subtrees but
    keeps a metadata-less checkpoint restorable)."""
    try:
        md = ckptr.metadata(path).item_metadata.tree

        def walk(sub, prefix=""):
            out = {}
            for k, v in sub.items():
                if isinstance(v, dict):
                    out[k] = walk(v, f"{prefix}{k}.")
                else:
                    out[k] = getattr(v, "name", None) or f"{prefix}{k}"
            return out

        if isinstance(md, dict):
            return walk(md)
    except Exception:
        pass
    return _unflatten_state_leaves(saved.keys())


def _unflatten_state_leaves(names):
    """Rebuild the `_state_pytree` nesting from dotted manifest names.

    The nesting is known by construction — {"model": {state_dict_key},
    "optimizer": {param_name: {slot}}} — which is what makes the
    dotted names (whose components themselves contain dots)
    unambiguous: a model leaf's key is everything after "model.", an
    optimizer leaf splits on the LAST dot into (param, slot)."""
    tree = {}
    for name in names:
        if name.startswith("model."):
            tree.setdefault("model", {})[name[len("model."):]] = name
        elif name.startswith("optimizer."):
            rest = name[len("optimizer."):]
            if "." not in rest:
                raise CheckpointError(
                    f"manifest optimizer leaf {name!r} has no slot "
                    "component")
            param, slot = rest.rsplit(".", 1)
            tree.setdefault("optimizer", {}).setdefault(param, {})[slot] \
                = name
        else:
            raise CheckpointError(
                f"manifest leaf {name!r} is outside the model/optimizer "
                "state tree — not a resilience-protocol checkpoint")
    return tree


def _target_shardings(model, optimizer, mesh):
    """Dotted leaf name -> target Sharding under the live mesh.

    Model leaves take their TAG-derived placement (`env.param_sharding`
    over the tensor's mesh_axes — the same single rule `shard_model`
    applies). Optimizer slots follow their parameter's placement when
    they are parameter-shaped (moments, velocity, master copies) and
    replicate otherwise (beta-power scalars). Empty with no mesh
    (plain single-device restore)."""
    from ..distributed import env as dist_env
    if mesh is None:
        return {}
    out = {}
    for k, t in model.state_dict().items():
        out[f"model.{k}"] = dist_env.param_sharding(t, mesh)
    if optimizer is not None:
        for pname, p in model.named_parameters():
            st = optimizer._states.get(id(p)) or {}
            psh = dist_env.param_sharding(p, mesh)
            pshape = tuple(p._value.shape)
            for slot, v in st.items():
                vshape = tuple(getattr(v, "shape", ()))
                out[f"optimizer.{pname}.{slot}"] = \
                    psh if vshape == pshape else dist_env.replicated(mesh)
    return out


def _load_resharded(path, model, optimizer, mesh):
    """The loader `CheckpointManager.restore(loader=...)` dispatches to:
    restore `path` (a step's arrays dir) into the live model/optimizer
    with per-leaf TARGET shardings. Shape mismatches raise naming the
    leaf (permanent — the retry layer fails fast on ValueError)."""
    import jax.numpy as jnp
    import orbax.checkpoint as ocp
    from ..distributed.checkpoint import _state_pytree

    step_dir = os.path.dirname(os.path.abspath(path))
    manifest = load_manifest(step_dir)
    saved = manifest.get("leaves") or {}
    if not saved:
        raise CheckpointError(
            f"{step_dir}: manifest carries no leaf table — cannot "
            "cross-check a reshard against it")

    # prime lazily-created optimizer slots so the checkpoint's
    # optimizer leaves find their in-memory targets (a fresh relaunch
    # has never run a step, so _states is empty until now)
    params = {k: p for k, p in model.named_parameters()}
    if optimizer is not None:
        for p in params.values():
            optimizer._get_state(p)
    target = _state_pytree(model, optimizer)
    live = _flat_leaves(target)

    shardings = _target_shardings(model, optimizer, mesh)

    # per-leaf manifest cross-check: every model leaf the live model
    # needs must exist with the same LOGICAL shape (layouts change
    # placement, never logical shape); dtype differences are cast at
    # restore like a same-layout resume
    missing = [n for n in live
               if n.startswith("model.") and n not in saved]
    if missing:
        raise CheckpointError(
            f"checkpoint at {step_dir} lacks model leaves the live "
            f"model requires: {missing[:4]}"
            + (f" (+{len(missing) - 4} more)" if len(missing) > 4 else ""))
    for name, meta in saved.items():
        v = live.get(name)
        if v is None:
            continue
        want = tuple(int(s) for s in meta.get("shape", ()))
        have = tuple(getattr(getattr(v, "_value", v), "shape", ()))
        if want != have:
            raise ValueError(
                f"reshard shape mismatch for leaf {name}: checkpoint "
                f"{want} vs live model {have} — a layout change moves "
                "shards, it never changes logical shapes")

    # restore args mirror the CHECKPOINT's tree (orbax requires the
    # exact structure), each matched leaf carrying its target Sharding
    # so every host reads only the shards it owns; leaves the live
    # process no longer wants (e.g. restoring without the optimizer)
    # degrade to host numpy and are dropped at write-back
    orphans = []
    ckptr = ocp.Checkpointer(ocp.PyTreeCheckpointHandler(use_ocdbt=False))
    structure = _restore_structure(ckptr, path, saved)

    def _args(sub):
        out = {}
        for k, v in sub.items():
            if isinstance(v, dict):
                out[k] = _args(v)
                continue
            name = v
            tgt = live.get(name)
            if tgt is None:
                orphans.append(name)
                out[k] = ocp.RestoreArgs()
                continue
            arr = getattr(tgt, "_value", tgt)
            sh = shardings.get(name)
            if sh is None:
                # no mesh: plain host restore (single-device relaunch)
                out[k] = ocp.RestoreArgs(restore_type=np.ndarray)
            else:
                out[k] = ocp.ArrayRestoreArgs(
                    sharding=sh, global_shape=tuple(arr.shape),
                    dtype=np.dtype(arr.dtype))
        return out

    restore_args = _args(structure)
    if orphans:
        warnings.warn(
            f"reshard: {len(orphans)} checkpoint leaves have no live "
            f"target and were dropped (first: {orphans[0]})",
            RuntimeWarning, stacklevel=3)
    restored = ckptr.restore(
        path, args=ocp.args.PyTreeRestore(restore_args=restore_args))

    # write back in place: model leaves onto their tensors (cast to
    # the live dtype — ArrayRestoreArgs already did, this is belt and
    # suspenders for the no-mesh numpy path), optimizer leaves onto
    # their slots
    sd = model.state_dict()
    for k, t in sd.items():
        if k in restored.get("model", {}):
            v = restored["model"][k]
            if not hasattr(v, "sharding"):
                v = jnp.asarray(v)
            t._value = v.astype(t._value.dtype) \
                if v.dtype != t._value.dtype else v
    if optimizer is not None:
        for pname, slots in restored.get("optimizer", {}).items():
            p = params.get(pname)
            if p is None:
                continue
            cur = optimizer._get_state(p)
            for sk, v in slots.items():
                if sk not in cur:
                    continue
                if not hasattr(v, "sharding"):
                    v = jnp.asarray(v)
                cur[sk] = v
    return restored


# ---------------------------------------------------------------------------
# the public entry
# ---------------------------------------------------------------------------

def reshard_restore(ckpt_dir, step=None, target_layout=None, mesh=None,
                    model=None, optimizer=None, manager=None, rank=0,
                    sink=None, retry=None):
    """Restore a PR-5 manifest checkpoint saved under ANY layout into
    the live model under `target_layout`. Returns the checkpoint's
    RunState (RNG re-seeded), or None when no checkpoint exists.

    ckpt_dir       CheckpointManager root (step_N subdirectories)
    step           exact step (corruption raises) or None for the
                   newest VALID checkpoint with the standard
                   newest -> oldest fallback past corrupt ones
    target_layout  planner Layout / axis dict the live process runs
                   under (defaults to the live mesh's layout)
    mesh           the live jax Mesh (defaults to the process mesh);
                   None restores plain single-device arrays
    manager        reuse an existing CheckpointManager (its retries,
                   sink and telemetry identity) instead of building one

    Every restore emits the usual `kind=ckpt` restore/fallback records
    plus one `kind=elastic` reshard_restore record referencing the
    committed step and BOTH layouts (tools/trace_check.py enforces
    that shape), and advances `elastic.reshard_restores`.
    """
    from ..distributed import env as dist_env
    if mesh is None:
        mesh = dist_env.current_mesh()
    target_layout = normalize_layout(target_layout) \
        if target_layout is not None else layout_from_mesh(mesh)
    mgr = manager
    owns = mgr is None
    if owns:
        mgr = CheckpointManager(ckpt_dir, model=model, optimizer=optimizer,
                                retry=retry, rank=rank, sink=sink)
    model = model if model is not None else mgr.model
    optimizer = optimizer if optimizer is not None else mgr.optimizer

    def _loader(path, model_, optimizer_):
        return _load_resharded(path, model_, optimizer_, mesh)

    try:
        rs = mgr.restore(step=step, model=model, optimizer=optimizer,
                         loader=_loader)
    finally:
        if owns:
            mgr.close()
    if rs is None:
        return None
    monitor.incr("elastic.reshard_restores")
    from ..telemetry.sink import emit_record, make_elastic_record
    rec = make_elastic_record(
        "reshard_restore", rank=rank, step=rs.step,
        layout_from=rs.layout or {"unknown": 1},
        layout_to=target_layout or {"unknown": 1})
    emit_record(rec, sink, mgr.sink if not owns else None)
    return rs
