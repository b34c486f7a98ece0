"""recompile-discipline: no kernel argument may trigger an unexpected
XLA retrace.

The perf stack's whole compile story (wavefront solve, prewarm pool,
persistent compile cache) rests on one discipline: every array entering
a ``@hot_path`` kernel is padded onto the power-of-two bucket lattice
(utils.vocab.pad_dim / pad_constraint_dim) with the dtypes the schema
contracts declare, so the set of XLA compile keys a workload generates
is exactly the bucket set.  A single un-bucketed dimension or silently
promoted dtype re-traces XLA and eats a 10-40 s compile on the hot
path.  This pass PROVES the discipline by abstract interpretation:

  encode     real ``SnapshotBuilder`` encodes at awkward raw sizes must
             land exactly on the lattice: every array unifies with its
             contract (analysis/contracts.py) under an axis environment
             where ``N``/``P`` are pinned to their pad buckets and
             free row axes must be constraint buckets;
  kernels    every solver kernel (greedy / wavefront / auction) driven
             through ``jax.eval_shape`` over contract-built abstract
             snapshots across the lattice must yield outputs matching
             the result contracts at every bucket — dtype-stable, no
             shape that depends on anything but the bucket;
  closure    the abstract input signatures (the compile keys) must be
             exactly one per lattice point, and the lattice must be
             closed under the gang-admission-retry subset solves
             (``num_pods_hint`` pins every binary-search subset into
             the full batch's bucket).

This module imports JAX and therefore runs as its own CLI mode
(``python -m kubernetes_tpu.analysis --shapes`` / ``make lint-shapes``)
and tier-1 test (tests/test_shapes.py), keeping ``make lint``
import-light.  The runtime complement is analysis/retrace.py: a
``GRAFTLINT_SHAPES=1``-armable tracker counting the retraces that
actually happen while tests and benches run.
"""

from __future__ import annotations

import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

from . import Finding, load_sources
from . import contracts as ct

CHECK = "recompile-discipline"

#: (node bucket, pod bucket) lattice the kernels are driven across.
#: Small buckets on purpose: eval_shape is tracing-only, but the solver
#: scan bodies are large programs.
LATTICE: Tuple[Tuple[int, int], ...] = ((8, 8), (16, 8), (16, 16), (32, 16))

#: raw (nodes, pods) sizes the encoder is validated at — deliberately
#: NOT powers of two (landing on the lattice is the encoder's doing)
#: and with n/p in DIFFERENT buckets, so an N/P axis swap cannot hide
ENCODE_SIZES: Tuple[Tuple[int, int], ...] = ((3, 12), (20, 2))

#: representative raw batch sizes for the gang-retry closure check
GANG_RETRY_SIZES: Tuple[int, ...] = (5, 8, 100, 1024)

#: (node, victim-slot, priority-level, pod) buckets the batched
#: preemption kernel is driven across (ops/preemption.py
#: batched_dry_run); the encoder pads with pad_dim(n, 8) / pad_dim(k, 4)
#: / pad_dim(l, 1) / pad_dim(p, 4) — see scheduler/preemption.py
PREEMPT_LATTICE: Tuple[Tuple[int, int, int, int], ...] = (
    (8, 4, 1, 4), (16, 4, 1, 4), (16, 4, 2, 8), (32, 8, 2, 8),
)

#: raw (candidate nodes, victims, levels, pods) sizes the preemption
#: encoder must land on the lattice from (closure check)
PREEMPT_RAW_SIZES: Tuple[Tuple[int, int, int, int], ...] = (
    (3, 1, 1, 2), (20, 5, 3, 9), (300, 17, 4, 16),
)


def _schema_contracts(root: str, package: str = "kubernetes_tpu"):
    files = load_sources(root, [os.path.join(package, "ops")])
    contracts: List[ct.Contract] = []
    for src in files:
        got, _issues = ct.collect(src)  # presence is tensor-contract's job
        contracts.extend(got)
    return ct.index_by_class(contracts)


# -- axis environments -------------------------------------------------------

def _class_env(
    cls: str, limits, n: int, p: int, rows: Dict[str, int]
) -> Dict[str, int]:
    """Concrete axis environment for one schema class.  ``rows`` sets
    the free constraint-row axes (default 1 = the no-constraints
    bucket); everything else derives from SnapshotLimits — the same
    derivations SnapshotBuilder uses, so drift fails the unify step."""
    from ..ops import schema

    r = rows.get("R", len(schema.FIXED_RESOURCES))
    tk = len(limits.topology_keys)
    common = {"N": n, "P": p, "R": r, "TK": tk}
    if cls == "ClusterTensors":
        return {
            **common,
            "LW": limits.label_words,
            "TW": limits.taint_words,
            "PW": limits.port_words,
            "IW": limits.image_words,
        }
    if cls == "SelectorTable":
        return {
            "S": rows.get("S", 1),
            "T": limits.max_terms,
            "E": limits.max_exprs,
            "K": limits.max_ids_per_expr,
        }
    if cls == "PreferredTable":
        return {
            "F": rows.get("F", 1),
            "E": limits.max_exprs,
            "K": limits.max_ids_per_expr,
        }
    if cls == "SpreadTable":
        return {**common, "C": rows.get("C", 1), "MC": limits.max_spread_per_pod}
    if cls == "TermTable":
        return {**common, "T": rows.get("T", 1), "MA": limits.max_pod_terms}
    if cls == "PodBatch":
        c = rows.get("classes", 1)
        return {
            **common,
            "TW": limits.taint_words,
            "PW": limits.port_words,
            "MT": limits.max_preferred,
            "C": c,
            "Cs": c,
            "Cc": rows.get("cons_classes", 1),
        }
    if cls == "PrefPodTable":
        return {**common, "U": rows.get("U", 1), "MA": limits.max_pod_terms}
    if cls == "ImageTable":
        return {**common, "I_pad": rows.get("I", 1), "MI": limits.max_pod_images}
    raise KeyError(f"no axis environment for schema class {cls}")


def _snapshot_classes():
    """Snapshot field name -> component class (resolved, not the string
    annotations)."""
    import typing

    from ..ops import schema

    hints = typing.get_type_hints(schema.Snapshot)
    return {f: hints[f] for f in schema.Snapshot._fields}


def abstract_snapshot(
    byclass, limits=None, n: int = 8, p: int = 8,
    rows: Optional[Dict[str, int]] = None,
):
    """A Snapshot of ShapeDtypeStructs built FROM the contracts — the
    contracts drive eval_shape, so schema/contract drift fails loudly."""
    import jax
    import numpy as np

    from ..ops import schema

    limits = limits or schema.SnapshotLimits()
    rows = rows or {}
    parts = {}
    for field, cls in _snapshot_classes().items():
        env = _class_env(cls.__name__, limits, n, p, rows)
        cfields = byclass.get(cls.__name__, {})
        vals = {}
        for f in cls._fields:
            c = cfields.get(f)
            if c is None:
                raise KeyError(
                    f"{cls.__name__}.{f} has no parsed contract (run the "
                    "tensor-contract pass first)"
                )
            vals[f] = jax.ShapeDtypeStruct(c.shape(env), np.dtype(c.dtype))
        parts[field] = cls(**vals)
    return schema.Snapshot(**parts)


# -- unification (real arrays vs contracts) ----------------------------------

def _is_pow2(x: int) -> bool:
    from ..utils.vocab import is_pad_bucket

    return is_pad_bucket(x, 1)


def _constraint_bucket_ok(x: int) -> bool:
    """pad_constraint_dim's range: 1 (no rows) or a power of two >= 32."""
    from ..utils.vocab import is_constraint_bucket

    return is_constraint_bucket(x)


def _unify_table(
    table, cfields: Dict[str, ct.Contract], env: Dict[str, int],
    free_row_axes: Sequence[str], where: str, findings: List[Finding],
    file: str, pow2_axes: Sequence[str] = (),
) -> None:
    """Check every array (or abstract ShapeDtypeStruct) of one table
    against its contract, binding free axes on first sight and requiring
    consistency afterwards.  ``free_row_axes`` must land on
    pad_constraint_dim buckets; ``pow2_axes`` on pad_dim(x, 1) buckets
    (the pod-class axes)."""
    env = dict(env)
    pend: List[Tuple[ct.Axis, int, str, int]] = []
    for f in type(table)._fields:
        arr = getattr(table, f)
        c = cfields.get(f)
        if c is None or arr is None or not hasattr(arr, "shape"):
            continue
        a = arr
        sym = f"{c.cls}.{f}"
        if str(a.dtype) != c.dtype:
            findings.append(
                Finding(
                    CHECK, file, c.line, sym,
                    f"{where}: dtype {a.dtype} != contract {c.render()}",
                )
            )
        if len(a.shape) != c.rank:
            findings.append(
                Finding(
                    CHECK, file, c.line, sym,
                    f"{where}: rank {len(a.shape)} != contract {c.render()}",
                )
            )
            continue
        for j, (axis, dim) in enumerate(zip(c.axes, a.shape)):
            if axis.sym is None:
                if dim != axis.const:
                    findings.append(
                        Finding(
                            CHECK, file, c.line, sym,
                            f"{where}: axis {j} = {dim}, contract "
                            f"{c.render()} pins it to {axis.const}",
                        )
                    )
                continue
            if axis.ceil:
                pend.append((axis, dim, sym, c.line))
                continue
            bound = env.get(axis.sym)
            if bound is None:
                env[axis.sym] = dim
                if axis.sym in free_row_axes and not _constraint_bucket_ok(dim):
                    findings.append(
                        Finding(
                            CHECK, file, c.line, sym,
                            f"{where}: free row axis {axis.sym} = {dim} is "
                            "not a pad_constraint_dim bucket (1 or a power "
                            "of two >= 32) — this shape recompiles per "
                            "composition",
                        )
                    )
                elif axis.sym in pow2_axes and not _is_pow2(dim):
                    findings.append(
                        Finding(
                            CHECK, file, c.line, sym,
                            f"{where}: free axis {axis.sym} = {dim} is not "
                            "a pad_dim power-of-two bucket — this shape "
                            "recompiles per composition",
                        )
                    )
            elif bound != dim:
                findings.append(
                    Finding(
                        CHECK, file, c.line, sym,
                        f"{where}: axis {axis.sym} = {dim} but {axis.sym} = "
                        f"{bound} elsewhere (contract {c.render()})",
                    )
                )
    for axis, dim, sym, line in pend:
        base = env.get(axis.sym)
        if base is None:
            continue
        want = math.ceil(base / axis.const)
        if dim != want:
            findings.append(
                Finding(
                    CHECK, file, line, sym,
                    f"{where}: ceil({axis.sym}/{axis.const}) = {want} "
                    f"(from {axis.sym}={base}), got {dim}",
                )
            )


#: Snapshot component class -> free (encode-determined) row axes that
#: must land on pad_constraint_dim buckets
_FREE_ROW_AXES = {
    "ClusterTensors": (),
    "SelectorTable": ("S",),
    "PreferredTable": ("F",),
    "SpreadTable": ("C",),
    "TermTable": ("T",),
    "PodBatch": (),
    "PrefPodTable": ("U",),
    "ImageTable": (),
}

#: free axes padded with pad_dim(x, 1): any power of two (pod-class and
#: image-vocab axes)
_POW2_AXES = {
    "PodBatch": ("C", "Cs", "Cc"),
    "ImageTable": ("I_pad",),
}


def _check_encode(byclass, findings: List[Finding]) -> None:
    """Real SnapshotBuilder encodes at awkward raw sizes must land on
    the lattice with contract dtypes everywhere."""
    from ..api import types as api
    from ..ops import schema
    from ..testing.wrappers import GI, MI, make_node, make_pod
    from ..utils import vocab as vb

    file = "kubernetes_tpu/ops/schema.py"
    for raw_n, raw_p in ENCODE_SIZES:
        builder = schema.SnapshotBuilder()
        nodes = [
            make_node(f"n{i}")
            .capacity(cpu_milli=4000, mem=8 * GI, pods=16)
            .zone(f"z{i % 2}")
            .obj()
            for i in range(raw_n)
        ]
        pods = []
        for i in range(raw_p):
            pw = (
                make_pod(f"p{i}")
                .req(cpu_milli=100, mem=128 * MI)
                .label("app", f"svc-{i % 2}")
            )
            if i % 2 == 0:
                pw.spread(
                    1, api.LABEL_ZONE, "DoNotSchedule", {"app": f"svc-{i % 2}"}
                )
            else:
                pw.pod_anti_affinity(
                    {"app": f"svc-{i % 2}"}, api.LABEL_HOSTNAME
                )
            pods.append(pw.obj())
        snap, meta = builder.build(nodes, pods)
        lim = builder.limits
        n_pad = vb.pad_dim(raw_n, lim.min_nodes)
        p_pad = vb.pad_dim(raw_p, lim.min_pods)
        rows = {"R": len(meta.resource_names)}
        for field, table in zip(type(snap)._fields, snap):
            cls = type(table).__name__
            env = _class_env(cls, lim, n_pad, p_pad, rows)
            # free axes bind to what the encoder produced; drop their
            # seeded defaults so unify sees them as free
            free = _FREE_ROW_AXES.get(cls, ())
            pow2 = _POW2_AXES.get(cls, ())
            env = {
                k: v for k, v in env.items()
                if k not in free and k not in pow2
            }
            _unify_table(
                table, byclass.get(cls, {}), env, free,
                f"encode[{raw_n}x{raw_p}].{field}", findings, file,
                pow2_axes=pow2,
            )


def _result_contract_check(
    result, cls_name: str, byclass, env: Dict[str, int], where: str,
    findings: List[Finding], file: str,
) -> None:
    """eval_shape output vs the result NamedTuple's contracts; component
    tables (SolveResult.cluster) recurse into their own contracts."""
    cfields = byclass.get(cls_name, {})
    for f in type(result)._fields:
        val = getattr(result, f)
        if val is None:
            continue
        c = cfields.get(f)
        if c is None:
            sub = type(val).__name__
            if sub in byclass:
                sub_env = {
                    k: env[k] for k in ("N", "P", "R", "TK", "LW", "TW",
                                        "PW", "IW") if k in env
                }
                _unify_table(
                    val, byclass[sub], sub_env, (), f"{where}.{f}",
                    findings, file,
                )
            continue
        want_shape = c.shape(env)
        if tuple(val.shape) != want_shape or str(val.dtype) != c.dtype:
            findings.append(
                Finding(
                    CHECK, file, c.line, f"{cls_name}.{f}",
                    f"{where}: eval_shape output {val.dtype}"
                    f"{tuple(val.shape)} != contract {c.render()} "
                    f"(= {c.dtype}{want_shape})",
                )
            )


def _check_kernels(byclass, findings: List[Finding]) -> None:
    """Drive the three solver kernels through eval_shape across the
    lattice; outputs must match the result contracts at every bucket
    and the abstract signature set must be exactly one per call."""
    import jax

    from ..ops import assign, auction, schema
    from . import retrace

    limits = schema.SnapshotLimits()
    ff_off = assign.FeatureFlags()

    def env_for(n, p, rows=None):
        env = _class_env("ClusterTensors", limits, n, p, rows or {})
        return env

    signatures = {"greedy": set(), "wavefront": set(), "auction": set()}
    calls = {"greedy": 0, "wavefront": 0, "auction": 0}

    for n, p in LATTICE:
        snap = abstract_snapshot(byclass, limits, n=n, p=p)

        # greedy scan
        calls["greedy"] += 1
        signatures["greedy"].add(
            retrace.signature(snap, (1, ff_off, 0))
        )
        try:
            res = jax.eval_shape(
                lambda s: assign.greedy_assign(
                    s, topo_z=1, features=ff_off, n_groups=0
                ),
                snap,
            )
            _result_contract_check(
                res, "SolveResult", byclass, env_for(n, p),
                f"greedy[{n}x{p}]", findings, "kubernetes_tpu/ops/assign.py",
            )
        except Exception as e:  # noqa: BLE001 — abstract eval failed
            findings.append(
                Finding(
                    CHECK, "kubernetes_tpu/ops/assign.py", 1,
                    "greedy_assign",
                    f"eval_shape failed at bucket {n}x{p}: {e}",
                )
            )

        # wavefront (wave plan is a device arg: i32[W_pad, K], the
        # same shape plan_waves pads to)
        from ..utils.vocab import pad_dim

        w_pad = pad_dim(max(-(-p // assign.DEFAULT_WAVE_CAP), 1), 8)
        members = jax.ShapeDtypeStruct(
            (w_pad, assign.DEFAULT_WAVE_CAP), "int32"
        )
        calls["wavefront"] += 1
        signatures["wavefront"].add(
            retrace.signature((snap, members), (1, ff_off, 0))
        )
        try:
            res = jax.eval_shape(
                lambda s, m: assign.wavefront_assign(
                    s, m, topo_z=1, features=ff_off, n_groups=0
                ),
                snap, members,
            )
            _result_contract_check(
                res, "SolveResult", byclass, env_for(n, p),
                f"wavefront[{n}x{p}]", findings,
                "kubernetes_tpu/ops/assign.py",
            )
        except Exception as e:  # noqa: BLE001
            findings.append(
                Finding(
                    CHECK, "kubernetes_tpu/ops/assign.py", 1,
                    "wavefront_assign",
                    f"eval_shape failed at bucket {n}x{p}: {e}",
                )
            )

        # auction (joint solve)
        tie_k = min(64, n)
        calls["auction"] += 1
        signatures["auction"].add(
            retrace.signature(snap, (0, ff_off, (1, 1), tie_k))
        )
        try:
            res = jax.eval_shape(
                lambda s: auction.auction_assign(
                    s, n_groups=0, features=ff_off, topo_z=(1, 1),
                    tie_k=tie_k,
                ),
                snap,
            )
            _result_contract_check(
                res, "AuctionResult", byclass, env_for(n, p),
                f"auction[{n}x{p}]", findings,
                "kubernetes_tpu/ops/auction.py",
            )
        except Exception as e:  # noqa: BLE001
            findings.append(
                Finding(
                    CHECK, "kubernetes_tpu/ops/auction.py", 1,
                    "auction_assign",
                    f"eval_shape failed at bucket {n}x{p}: {e}",
                )
            )

    # a constraint-family flip IS a distinct compile key (the prewarm
    # pool compiles the flipped variant for exactly this reason): the
    # spread-enabled signature must differ from the base one
    n, p = 16, 16
    snap_sp = abstract_snapshot(
        byclass, limits, n=n, p=p, rows={"C": 32}
    )
    ff_sp = assign.FeatureFlags(spread=True, spread_slots=(1,))
    sig_sp = retrace.signature(snap_sp, (8, ff_sp, 0))
    if sig_sp in signatures["greedy"]:
        findings.append(
            Finding(
                CHECK, "kubernetes_tpu/ops/assign.py", 1, "greedy_assign",
                "spread-enabled signature collides with a base-lattice "
                "compile key (feature flags must be part of the key)",
            )
        )
    try:
        res = jax.eval_shape(
            lambda s: assign.greedy_assign(
                s, topo_z=8, features=ff_sp, n_groups=0
            ),
            snap_sp,
        )
        _result_contract_check(
            res, "SolveResult", byclass, env_for(n, p),
            f"greedy+spread[{n}x{p}]", findings,
            "kubernetes_tpu/ops/assign.py",
        )
    except Exception as e:  # noqa: BLE001
        findings.append(
            Finding(
                CHECK, "kubernetes_tpu/ops/assign.py", 1, "greedy_assign",
                f"eval_shape (spread features) failed at {n}x{p}: {e}",
            )
        )

    for label, sigs in signatures.items():
        if len(sigs) != calls[label]:
            findings.append(
                Finding(
                    CHECK, "kubernetes_tpu/ops/assign.py", 1, label,
                    f"{calls[label]} lattice points produced "
                    f"{len(sigs)} distinct compile keys — the abstract "
                    "signature set must be exactly the bucket set",
                )
            )


#: (slice-count, torus-extent) buckets the slice carve-out kernels are
#: driven across (ops/slices.py; features.slice_z / slice_dim are both
#: pad_dim powers of two, so they stay on the executable-key lattice)
SLICE_LATTICE: Tuple[Tuple[int, int], ...] = ((1, 2), (2, 2), (4, 4))


def _check_slice_kernels(byclass, findings: List[Finding]) -> None:
    """Slice carve-out coverage: the greedy solver with the slice family
    armed must eval_shape across the (slice_z, slice_dim) lattice with
    contract-stable SolveResult outputs (carve-out telemetry scalars
    included), one compile key per bucket, distinct from the base keys
    — and the sharded twin's keys distinct from the single-chip ones.
    The standalone fragmentation kernel is checked against the
    SliceStats contracts at every bucket."""
    import jax
    import numpy as np

    from ..ops import assign, schema
    from ..ops import slices as slices_ops
    from ..parallel import sharded
    from . import retrace

    file = "kubernetes_tpu/ops/slices.py"
    limits = schema.SnapshotLimits()
    n, p = 16, 8
    snap = abstract_snapshot(byclass, limits, n=n, p=p)
    stats_fields = byclass.get("SliceStats", {})
    if not stats_fields:
        findings.append(
            Finding(
                CHECK, file, 1, "SliceStats",
                "slice-stats contracts missing (run the tensor-contract "
                "pass first)",
            )
        )
        return

    base_sig = retrace.signature(snap, (1, assign.FeatureFlags(), 0))
    sigs = set()
    for policy_require in (False, True):
        for sz, sd in SLICE_LATTICE:
            ff = assign.FeatureFlags(
                slices=True, slice_require=policy_require,
                slice_z=sz, slice_dim=sd,
            )
            sig = retrace.signature(snap, (1, ff, 4))
            sigs.add(sig)
            if sig == base_sig:
                findings.append(
                    Finding(
                        CHECK, file, 1, "carveout_eval",
                        "slice-enabled compile key collides with the base "
                        "key (slice feature flags must be part of the key)",
                    )
                )
            try:
                res = jax.eval_shape(
                    lambda s, ff=ff: assign.greedy_assign(
                        s, topo_z=1, features=ff, n_groups=4
                    ),
                    snap,
                )
            except Exception as e:  # noqa: BLE001 — abstract eval failed
                findings.append(
                    Finding(
                        CHECK, file, 1, "carveout_eval",
                        f"eval_shape failed at slice bucket "
                        f"{sz}x{sd} (require={policy_require}): {e}",
                    )
                )
                continue
            env = _class_env("ClusterTensors", limits, n, p, {})
            _result_contract_check(
                res, "SolveResult", byclass, env,
                f"greedy+slices[{sz}x{sd}]", findings,
                "kubernetes_tpu/ops/assign.py",
            )
            for f in ("frag_score", "carveouts", "contiguous_gangs",
                      "carveout_fallbacks"):
                if getattr(res, f, None) is None:
                    findings.append(
                        Finding(
                            CHECK, file, 1, f,
                            f"slice-family solve returned no {f} at "
                            f"bucket {sz}x{sd}",
                        )
                    )
            # fragmentation kernel vs SliceStats contracts
            try:
                stats = jax.eval_shape(
                    lambda c, sz=sz, sd=sd: slices_ops.fragmentation(
                        c, sz, sd
                    ),
                    snap.cluster,
                )
            except Exception as e:  # noqa: BLE001
                findings.append(
                    Finding(
                        CHECK, file, 1, "fragmentation",
                        f"eval_shape failed at slice bucket {sz}x{sd}: {e}",
                    )
                )
                continue
            senv = {"S": sz}
            for f in slices_ops.SliceStats._fields:
                c = stats_fields.get(f)
                val = getattr(stats, f)
                if c is None:
                    continue
                want = c.shape(senv)
                if tuple(val.shape) != want or str(val.dtype) != c.dtype:
                    findings.append(
                        Finding(
                            CHECK, file, c.line, f"SliceStats.{f}",
                            f"slices[{sz}x{sd}]: eval_shape output "
                            f"{val.dtype}{tuple(val.shape)} != contract "
                            f"{c.render()} (= {c.dtype}{want})",
                        )
                    )
    want_sigs = 2 * len(SLICE_LATTICE)
    if len(sigs) != want_sigs:
        findings.append(
            Finding(
                CHECK, file, 1, "carveout_eval",
                f"{want_sigs} slice lattice points produced {len(sigs)} "
                "distinct compile keys — slice_z/slice_dim/slice_require "
                "must each be part of the key",
            )
        )
    # sharded twin: the mesh shape must discriminate slice keys too
    ndev = len(jax.devices())
    size = 1
    while size * 2 <= min(ndev, 8):
        size *= 2
    mesh = sharded.make_mesh(size)
    mesh_sig = sharded.mesh_signature(mesh)
    ff = assign.FeatureFlags(slices=True, slice_z=2, slice_dim=2)
    if retrace.signature(snap, (1, ff, 4, mesh_sig)) == retrace.signature(
        snap, (1, ff, 4)
    ):
        findings.append(
            Finding(
                CHECK, file, 1, "carveout_eval",
                "sharded slice compile key collides with the single-chip "
                "key (mesh shape must be part of the signature)",
            )
        )
    if n % size == 0:
        try:
            res = jax.eval_shape(
                lambda s: sharded.sharded_greedy_assign(
                    s, mesh, topo_z=1, features=ff, n_groups=4
                ),
                snap,
            )
            if getattr(res, "frag_score", None) is None:
                findings.append(
                    Finding(
                        CHECK, file, 1, "frag_score",
                        "sharded slice-family solve returned no frag_score",
                    )
                )
        except Exception as e:  # noqa: BLE001
            findings.append(
                Finding(
                    CHECK, file, 1, "sharded_greedy_assign",
                    f"sharded slice eval_shape failed: {e}",
                )
            )


#: (node bucket, slot-capacity bucket, dirty-row bucket, insert bucket,
#: batch-class bucket) lattice the incremental-solve partials kernels
#: are driven across (ops/partials.py; models/partials.py pads every
#: index bucket with pad_dim)
PARTIALS_LATTICE: Tuple[Tuple[int, int, int, int, int], ...] = (
    (8, 32, 8, 1, 1), (16, 32, 8, 2, 2), (16, 64, 16, 2, 4),
)


def _check_partials_kernels(byclass, findings: List[Finding]) -> None:
    """Drive the incremental-solve partials kernels (ops/partials.py)
    through eval_shape across PARTIALS_LATTICE: outputs must match the
    ClassSpecs/PartialsStore/ClassStatics contracts at every bucket,
    the abstract signature set must be exactly one per lattice point,
    and the WARM solver twin must (a) eval_shape to the same SolveResult
    contracts as the cold one and (b) carry a compile key distinct from
    it — warm and cold are different executables by construction (the
    statics operands are part of the signature), single-chip and
    sharded alike."""
    import jax
    import numpy as np

    from ..ops import assign, partials as pops, schema
    from ..parallel import sharded
    from . import retrace

    file = "kubernetes_tpu/ops/partials.py"
    limits = schema.SnapshotLimits()
    spec_fields = byclass.get("ClassSpecs", {})
    store_fields = byclass.get("PartialsStore", {})
    statics_fields = byclass.get("ClassStatics", {})
    if not spec_fields or not store_fields or not statics_fields:
        findings.append(
            Finding(
                CHECK, file, 1, "ClassSpecs",
                "partials contracts missing (run the tensor-contract "
                "pass first)",
            )
        )
        return

    def env_for(n, g, d, m, c):
        return {
            "N": n, "G": g, "D": d, "M": m, "C": c,
            "T": limits.max_terms, "E": limits.max_exprs,
            "K": limits.max_ids_per_expr, "MT": limits.max_preferred,
            "TW": limits.taint_words, "PW": limits.port_words,
        }

    def abstract(cls, cfields, env):
        vals = {}
        for f in cls._fields:
            contract = cfields.get(f)
            if contract is None:
                raise KeyError(f"{cls.__name__}.{f} has no contract")
            vals[f] = jax.ShapeDtypeStruct(
                contract.shape(env), np.dtype(contract.dtype)
            )
        return cls(**vals)

    def check_out(result, cls_name, cfields, env, where):
        for f in type(result)._fields:
            contract = cfields.get(f)
            val = getattr(result, f)
            if contract is None:
                continue
            want = contract.shape(env)
            if tuple(val.shape) != want or str(val.dtype) != contract.dtype:
                findings.append(
                    Finding(
                        CHECK, file, contract.line, f"{cls_name}.{f}",
                        f"{where}: eval_shape output {val.dtype}"
                        f"{tuple(val.shape)} != contract "
                        f"{contract.render()} (= {contract.dtype}{want})",
                    )
                )

    signatures = {"eval": set(), "refresh": set(), "insert": set(),
                  "gather": set()}
    for n, g, d, m, c in PARTIALS_LATTICE:
        env = env_for(n, g, d, m, c)
        snap = abstract_snapshot(byclass, limits, n=n, p=8)
        cluster = snap.cluster
        specs = abstract(pops.ClassSpecs, spec_fields, env)
        store = abstract(
            pops.PartialsStore, store_fields, {"G": g, "N": n}
        )
        didx = jax.ShapeDtypeStruct((d,), "int32")
        midx = jax.ShapeDtypeStruct((m,), "int32")
        slots = jax.ShapeDtypeStruct((c,), "int32")
        try:
            out = jax.eval_shape(pops.eval_store, cluster, specs)
            check_out(
                out, "PartialsStore", store_fields, {"G": g, "N": n},
                f"eval_store[{n}x{g}]",
            )
            signatures["eval"].add(retrace.signature((cluster, specs)))
            out = jax.eval_shape(
                pops.refresh_rows, store, specs, cluster, didx
            )
            check_out(
                out, "PartialsStore", store_fields, {"G": g, "N": n},
                f"refresh_rows[{n}x{g}x{d}]",
            )
            signatures["refresh"].add(
                retrace.signature((store, specs, cluster, didx))
            )
            out = jax.eval_shape(
                pops.insert_slots, store, specs, cluster, midx
            )
            check_out(
                out, "PartialsStore", store_fields, {"G": g, "N": n},
                f"insert_slots[{n}x{g}x{m}]",
            )
            signatures["insert"].add(
                retrace.signature((store, specs, cluster, midx))
            )
            out = jax.eval_shape(pops.gather_statics, store, slots)
            check_out(
                out, "ClassStatics", statics_fields, {"C": c, "N": n},
                f"gather_statics[{n}x{g}x{c}]",
            )
            signatures["gather"].add(retrace.signature((store, slots)))
        except Exception as e:  # noqa: BLE001 — abstract eval failed
            findings.append(
                Finding(
                    CHECK, file, 1, "partials",
                    f"eval_shape failed at bucket "
                    f"{(n, g, d, m, c)}: {e}",
                )
            )
    for label, sigs in signatures.items():
        if len(sigs) != len(PARTIALS_LATTICE):
            findings.append(
                Finding(
                    CHECK, file, 1, label,
                    f"{len(PARTIALS_LATTICE)} lattice points produced "
                    f"{len(sigs)} distinct compile keys — the abstract "
                    "signature set must be exactly the bucket set",
                )
            )

    # WARM vs COLD solver twins: same SolveResult contracts, DISTINCT
    # compile keys (single-chip and sharded — the statics operands and
    # the mesh shape are both part of the signature)
    n, p, c = 16, 8, 2
    ff_off = assign.FeatureFlags()
    snap = abstract_snapshot(byclass, limits, n=n, p=p)
    statics = abstract(
        pops.ClassStatics, statics_fields, {"C": c, "N": n}
    )
    cold_sig = retrace.signature(snap, (1, ff_off, 0))
    warm_sig = retrace.signature((snap, statics), (1, ff_off, 0))
    if warm_sig == cold_sig:
        findings.append(
            Finding(
                CHECK, file, 1, "ClassStatics",
                "warm compile key collides with the cold key (the "
                "statics operands must be part of the signature)",
            )
        )
    try:
        res = jax.eval_shape(
            lambda s, st: assign.greedy_assign(
                s, topo_z=1, features=ff_off, n_groups=0, statics=st
            ),
            snap, statics,
        )
        _result_contract_check(
            res, "SolveResult", byclass,
            _class_env("ClusterTensors", limits, n, p, {}),
            f"greedy-warm[{n}x{p}]", findings,
            "kubernetes_tpu/ops/assign.py",
        )
    except Exception as e:  # noqa: BLE001
        findings.append(
            Finding(
                CHECK, file, 1, "greedy_assign",
                f"warm eval_shape failed at bucket {n}x{p}: {e}",
            )
        )
    ndev = len(jax.devices())
    size = 1
    while size * 2 <= min(ndev, 8):
        size *= 2
    mesh = sharded.make_mesh(size)
    mesh_sig = sharded.mesh_signature(mesh)
    if retrace.signature(
        (snap, statics), (1, ff_off, 0, mesh_sig)
    ) == warm_sig:
        findings.append(
            Finding(
                CHECK, file, 1, "ClassStatics",
                "sharded warm compile key collides with the single-chip "
                "warm key (mesh shape must be part of the signature)",
            )
        )
    if n % size == 0:
        try:
            res = jax.eval_shape(
                lambda s, st: sharded.sharded_greedy_assign(
                    s, mesh, topo_z=1, features=ff_off, n_groups=0,
                    statics=st,
                ),
                snap, statics,
            )
            _result_contract_check(
                res, "SolveResult", byclass,
                _class_env("ClusterTensors", limits, n, p, {}),
                f"greedy-sharded-warm[{n}x{p}]", findings,
                "kubernetes_tpu/parallel/sharded.py",
            )
        except Exception as e:  # noqa: BLE001
            findings.append(
                Finding(
                    CHECK, file, 1, "sharded_greedy_assign",
                    f"sharded warm eval_shape failed: {e}",
                )
            )


def _check_axis_transitions(byclass, findings: List[Finding]) -> None:
    """Elastic node axis (ISSUE 15): drive a REAL ClusterState through
    growth and shrink across pad buckets and prove the compile-key
    story end to end:

      * every exposed bucket is a pad bucket and growth is eager
        (monotone while adding);
      * WITHIN-bucket growth — more rows in the same bucket, or a
        backing-array realloc — provably reuses the existing keys (the
        exposed shapes are identical) and never bumps the struct
        generation;
      * each bucket CROSSING yields exactly one new compile key per
        kernel family — greedy cold, greedy WARM (partials statics) and
        the SHARDED twin included — i.e. the abstract-signature set
        equals the observed-bucket set for every family;
      * the lattice is closed under node-axis growth AND shrink: the
        post-dwell shrink lands exactly on a previously observed
        bucket, so the shrink re-uses an existing key instead of
        minting one (and the dwell pins the bucket until it is
        served)."""
    import jax
    import numpy as np

    from ..api import types as api
    from ..ops import assign, partials as pops, schema
    from ..parallel import sharded
    from ..utils import vocab as vbu
    from . import retrace

    file = "kubernetes_tpu/ops/schema.py"
    limits = schema.SnapshotLimits()
    state = schema.ClusterState(schema.SnapshotBuilder(limits))
    dwell = 3
    state.configure_elastic_axis(shrink_dwell=dwell)
    start = vbu.pad_dim(0, limits.min_nodes)

    def mk_node(i):
        node = api.Node(meta=api.ObjectMeta(name=f"ax-{i}", namespace=""))
        node.meta.labels[api.LABEL_HOSTNAME] = f"ax-{i}"
        node.status.allocatable = {
            api.CPU: 1000, api.MEMORY: 1 << 20, api.PODS: 16,
        }
        node.status.capacity = dict(node.status.allocatable)
        return node

    # -- growth walk: eager, pad-bucketed, shape-stable within a bucket --
    struct0 = state.struct_generation
    buckets: List[int] = []
    prev_shapes = None
    total = 4 * start + 1  # two crossings past the floor bucket
    for i in range(total):
        state.add_node(mk_node(i))
        t = state.tensors()
        n = int(t.allocatable.shape[0])
        shapes = tuple(np.shape(leaf) for leaf in t)
        if not vbu.is_pad_bucket(n, 1):
            findings.append(
                Finding(
                    CHECK, file, 1, "ClusterState.tensors",
                    f"exposed node axis {n} at {i + 1} nodes is not a "
                    "pad bucket",
                )
            )
            return
        if buckets and n < buckets[-1]:
            findings.append(
                Finding(
                    CHECK, file, 1, "ClusterState.tensors",
                    f"bucket shrank {buckets[-1]} -> {n} while ADDING "
                    "nodes (growth must be eager)",
                )
            )
        if buckets and n == buckets[-1] and shapes != prev_shapes:
            findings.append(
                Finding(
                    CHECK, file, 1, "ClusterState.tensors",
                    f"within-bucket add at {i + 1} nodes changed the "
                    "exposed shapes — the existing compile keys must be "
                    "reused",
                )
            )
        if not buckets or n != buckets[-1]:
            buckets.append(n)
        prev_shapes = shapes
    if state.struct_generation != struct0:
        findings.append(
            Finding(
                CHECK, file, 1, "ClusterState._grow",
                "node-axis growth bumped the struct generation — "
                "row-preserving reallocs must not force full resyncs",
            )
        )
    if len(buckets) < 3:
        findings.append(
            Finding(
                CHECK, file, 1, "ClusterState.tensors",
                f"growth walk observed buckets {buckets}; expected at "
                "least two crossings",
            )
        )
        return

    # -- within-bucket backing realloc: shapes and struct gen both hold --
    shapes0 = tuple(np.shape(leaf) for leaf in state.tensors())
    g0 = state.struct_generation
    state._grow(state._cap * 2)
    if state.struct_generation != g0:
        findings.append(
            Finding(
                CHECK, file, 1, "ClusterState._grow",
                "explicit backing-array grow bumped the struct "
                "generation",
            )
        )
    if tuple(np.shape(leaf) for leaf in state.tensors()) != shapes0:
        findings.append(
            Finding(
                CHECK, file, 1, "ClusterState._grow",
                "backing-array grow changed the exposed shapes without "
                "a bucket crossing",
            )
        )

    # -- one compile key per kernel family per observed bucket -----------
    p = 8
    ff_off = assign.FeatureFlags()
    spec_fields = byclass.get("ClassStatics", {})
    ndev = len(jax.devices())
    size = 1
    while size * 2 <= min(ndev, 8):
        size *= 2
    mesh = sharded.make_mesh(size)
    mesh_sig = sharded.mesh_signature(mesh)
    sigs = {"greedy": set(), "greedy-warm": set(), "greedy-sharded": set()}
    for n in buckets:
        snap = abstract_snapshot(byclass, limits, n=n, p=p)
        sigs["greedy"].add(retrace.signature(snap, (1, ff_off, 0)))
        if spec_fields:
            statics = pops.ClassStatics(
                **{
                    f: jax.ShapeDtypeStruct(
                        spec_fields[f].shape({"C": 2, "N": n}),
                        np.dtype(spec_fields[f].dtype),
                    )
                    for f in pops.ClassStatics._fields
                }
            )
            sigs["greedy-warm"].add(
                retrace.signature((snap, statics), (1, ff_off, 0))
            )
        sigs["greedy-sharded"].add(
            retrace.signature(snap, (1, ff_off, 0, mesh_sig))
        )
        try:
            res = jax.eval_shape(
                lambda s: assign.greedy_assign(
                    s, topo_z=1, features=ff_off, n_groups=0
                ),
                snap,
            )
            _result_contract_check(
                res, "SolveResult", byclass,
                _class_env("ClusterTensors", limits, n, p, {}),
                f"greedy-axis[{n}x{p}]", findings,
                "kubernetes_tpu/ops/assign.py",
            )
        except Exception as e:  # noqa: BLE001 — abstract eval failed
            findings.append(
                Finding(
                    CHECK, file, 1, "greedy_assign",
                    f"eval_shape failed at grown bucket {n}: {e}",
                )
            )
    for fam, got in sigs.items():
        if fam == "greedy-warm" and not spec_fields:
            continue
        if len(got) != len(buckets):
            findings.append(
                Finding(
                    CHECK, file, 1, fam,
                    f"{len(buckets)} observed buckets produced "
                    f"{len(got)} {fam} compile keys — a bucket crossing "
                    "must mint exactly one new key per kernel family",
                )
            )

    # -- shrink: dwell pins the bucket, then lands on a KNOWN bucket -----
    peak = buckets[-1]
    for i in range(total - start):
        state.remove_node(f"ax-{i}")
    for k in range(dwell + 1):
        # one generation per tick (the dwell counts generations, not
        # tensors() calls)
        state.add_node(mk_node(10_000 + k))
        state.remove_node(f"ax-{10_000 + k}")
        t = state.tensors()
        n = int(t.allocatable.shape[0])
        if k < dwell - 1 and n != peak:
            findings.append(
                Finding(
                    CHECK, file, 1, "ClusterState.tensors",
                    f"bucket moved to {n} after only {k + 1} "
                    f"below-bucket generation(s); the dwell is {dwell}",
                )
            )
    final = int(state.tensors().allocatable.shape[0])
    if final == peak:
        findings.append(
            Finding(
                CHECK, file, 1, "ClusterState.tensors",
                f"post-dwell shrink never served: bucket still {peak}",
            )
        )
    elif final not in buckets:
        findings.append(
            Finding(
                CHECK, file, 1, "ClusterState.tensors",
                f"shrink landed on {final}, never observed during "
                f"growth ({buckets}) — shrink must REUSE an existing "
                "compile key (lattice closure)",
            )
        )


def _check_gang_retry_closure(findings: List[Finding]) -> None:
    """The gang-admission binary search re-solves SUBSETS of the batch
    with num_pods_hint pinned to the full batch size: every subset must
    land in the full batch's pad bucket (one executable for the whole
    search, not one per subset size)."""
    from ..ops import schema
    from ..utils import vocab as vb

    min_pods = schema.SnapshotLimits().min_pods
    for full in GANG_RETRY_SIZES:
        bucket = vb.pad_dim(full, min_pods)
        bad = [
            k for k in range(1, full + 1)
            if vb.pad_dim(max(k, full), min_pods) != bucket
        ]
        if bad:
            findings.append(
                Finding(
                    CHECK, "kubernetes_tpu/utils/vocab.py", 1, "pad_dim",
                    f"bucket lattice not closed under gang-retry subsets "
                    f"of a {full}-pod batch: sizes {bad[:5]} escape bucket "
                    f"{bucket}",
                )
            )


def _check_preemption_kernel(byclass, findings: List[Finding]) -> None:
    """Drive the batched preemption dry-run (ops/preemption.py
    batched_dry_run) through eval_shape across PREEMPT_LATTICE: outputs
    must match the BatchDryRunResult contracts at every bucket, the
    abstract signature set must be exactly one per lattice point, and
    the encoder's pad buckets must be closed over the raw (candidate,
    victim, level, pod) sizes a PostFilter pass produces."""
    import jax
    import numpy as np

    from ..ops import preemption as pre_ops
    from ..ops import schema
    from ..utils import vocab as vb
    from . import retrace

    file = "kubernetes_tpu/ops/preemption.py"
    r = len(schema.FIXED_RESOURCES)
    batch_fields = byclass.get("PreemptionBatch", {})
    result_fields = byclass.get("BatchDryRunResult", {})
    if not batch_fields or not result_fields:
        findings.append(
            Finding(
                CHECK, file, 1, "PreemptionBatch",
                "preemption batch contracts missing (run the "
                "tensor-contract pass first)",
            )
        )
        return

    def abstract_batch(env):
        vals = {}
        for f in pre_ops.PreemptionBatch._fields:
            c = batch_fields.get(f)
            if c is None:
                raise KeyError(f"PreemptionBatch.{f} has no contract")
            vals[f] = jax.ShapeDtypeStruct(c.shape(env), np.dtype(c.dtype))
        return pre_ops.PreemptionBatch(**vals)

    signatures = set()
    for n, k, l, p in PREEMPT_LATTICE:
        env = {"N": n, "K": k, "L": l, "P": p, "R": r}
        batch = abstract_batch(env)
        signatures.add(retrace.signature(batch))
        try:
            res = jax.eval_shape(pre_ops.batched_dry_run, batch)
        except Exception as e:  # noqa: BLE001 — abstract eval failed
            findings.append(
                Finding(
                    CHECK, file, 1, "batched_dry_run",
                    f"eval_shape failed at bucket {n}x{k}x{l}x{p}: {e}",
                )
            )
            continue
        for f in pre_ops.BatchDryRunResult._fields:
            c = result_fields.get(f)
            val = getattr(res, f)
            if c is None:
                continue
            want = c.shape(env)
            if tuple(val.shape) != want or str(val.dtype) != c.dtype:
                findings.append(
                    Finding(
                        CHECK, file, c.line, f"BatchDryRunResult.{f}",
                        f"preempt[{n}x{k}x{l}x{p}]: eval_shape output "
                        f"{val.dtype}{tuple(val.shape)} != contract "
                        f"{c.render()} (= {c.dtype}{want})",
                    )
                )
    if len(signatures) != len(PREEMPT_LATTICE):
        findings.append(
            Finding(
                CHECK, file, 1, "batched_dry_run",
                f"{len(PREEMPT_LATTICE)} lattice points produced "
                f"{len(signatures)} distinct compile keys — the abstract "
                "signature set must be exactly the bucket set",
            )
        )
    # closure: every raw (candidate, victim, level, pod) size a pass
    # can produce must pad onto the power-of-two lattice family
    for raw_n, raw_k, raw_l, raw_p in PREEMPT_RAW_SIZES:
        padded = (
            vb.pad_dim(raw_n, 8), vb.pad_dim(raw_k, 4),
            vb.pad_dim(raw_l, 1), vb.pad_dim(raw_p, 4),
        )
        if not all(vb.is_pad_bucket(d, 1) for d in padded):
            findings.append(
                Finding(
                    CHECK, file, 1, "PreemptionBatch",
                    f"raw preemption sizes {(raw_n, raw_k, raw_l, raw_p)} "
                    f"pad to {padded} — not closed over the "
                    "power-of-two bucket family",
                )
            )
    # the batched static-feasibility dispatch reuses the snapshot
    # contracts: one eval at the base lattice point proves the vmapped
    # kernel is shape-stable over contract-built components
    from ..ops import schema as _schema

    limits = _schema.SnapshotLimits()
    snap = abstract_snapshot(byclass, limits, n=8, p=8)
    try:
        out = jax.eval_shape(
            pre_ops.static_feasible_batch,
            snap.cluster, snap.pods, snap.selectors,
        )
        if tuple(out.shape) != (8, 8) or str(out.dtype) != "bool":
            findings.append(
                Finding(
                    CHECK, file, 1, "static_feasible_batch",
                    f"static mask eval_shape produced {out.dtype}"
                    f"{tuple(out.shape)}, want bool[P, N]",
                )
            )
    except Exception as e:  # noqa: BLE001
        findings.append(
            Finding(
                CHECK, file, 1, "static_feasible_batch",
                f"eval_shape failed: {e}",
            )
        )


def _check_mesh_kernels(byclass, findings: List[Finding]) -> None:
    """Mesh-sharded solver twins driven through eval_shape across the
    lattice: outputs must match the result contracts at every bucket,
    the abstract signature set must be exactly one per (bucket, mesh
    shape) — the mesh shape IS part of the executable key — and every
    lattice node bucket must split evenly across the mesh (buckets and
    mesh sizes are both powers of two; smaller-than-mesh buckets are
    the counted single-chip fallback, not a compile surface).

    The mesh uses the largest power-of-two device count available
    (capped at 8): under the forced-host-platform test
    environment that is a real 8-way mesh; a bare 1-device run still
    exercises the shard_map signatures."""
    import jax

    from ..ops import assign, schema
    from ..parallel import sharded
    from . import retrace

    ndev = len(jax.devices())
    size = 1
    while size * 2 <= min(ndev, 8):
        size *= 2
    mesh = sharded.make_mesh(size)
    mesh_sig = sharded.mesh_signature(mesh)
    file = "kubernetes_tpu/parallel/sharded.py"

    limits = schema.SnapshotLimits()
    ff_off = assign.FeatureFlags()

    def env_for(n, p):
        return _class_env("ClusterTensors", limits, n, p, {})

    signatures = {
        "greedy-sharded": set(), "wavefront-sharded": set(),
        "auction-sharded": set(),
    }
    calls = {"greedy-sharded": 0, "wavefront-sharded": 0,
             "auction-sharded": 0}
    from ..utils.vocab import pad_dim

    for n, p in LATTICE:
        if n % size:
            findings.append(
                Finding(
                    CHECK, file, 1, "make_mesh",
                    f"lattice node bucket {n} does not split across the "
                    f"{size}-device mesh — pad buckets and mesh sizes "
                    "must share the power-of-two family",
                )
            )
            continue
        snap = abstract_snapshot(byclass, limits, n=n, p=p)

        calls["greedy-sharded"] += 1
        signatures["greedy-sharded"].add(
            retrace.signature(snap, (1, ff_off, 0, mesh_sig))
        )
        try:
            res = jax.eval_shape(
                lambda s: sharded.sharded_greedy_assign(
                    s, mesh, topo_z=1, features=ff_off, n_groups=0
                ),
                snap,
            )
            _result_contract_check(
                res, "SolveResult", byclass, env_for(n, p),
                f"greedy-sharded[{n}x{p}]", findings, file,
            )
        except Exception as e:  # noqa: BLE001 — abstract eval failed
            findings.append(
                Finding(
                    CHECK, file, 1, "sharded_greedy_assign",
                    f"eval_shape failed at bucket {n}x{p}: {e}",
                )
            )

        w_pad = pad_dim(max(-(-p // assign.DEFAULT_WAVE_CAP), 1), 8)
        members = jax.ShapeDtypeStruct(
            (w_pad, assign.DEFAULT_WAVE_CAP), "int32"
        )
        calls["wavefront-sharded"] += 1
        signatures["wavefront-sharded"].add(
            retrace.signature((snap, members), (1, ff_off, 0, mesh_sig))
        )
        try:
            res = jax.eval_shape(
                lambda s, m: sharded.sharded_wavefront_assign(
                    s, m, mesh, topo_z=1, features=ff_off, n_groups=0
                ),
                snap, members,
            )
            _result_contract_check(
                res, "SolveResult", byclass, env_for(n, p),
                f"wavefront-sharded[{n}x{p}]", findings, file,
            )
        except Exception as e:  # noqa: BLE001
            findings.append(
                Finding(
                    CHECK, file, 1, "sharded_wavefront_assign",
                    f"eval_shape failed at bucket {n}x{p}: {e}",
                )
            )

        tie_k = min(64, n)
        calls["auction-sharded"] += 1
        signatures["auction-sharded"].add(
            retrace.signature(snap, (0, ff_off, (1, 1), tie_k, mesh_sig))
        )
        try:
            res = jax.eval_shape(
                lambda s: sharded.sharded_auction_assign(
                    s, mesh, n_groups=0, features=ff_off, topo_z=(1, 1),
                    tie_k=tie_k,
                ),
                snap,
            )
            _result_contract_check(
                res, "AuctionResult", byclass, env_for(n, p),
                f"auction-sharded[{n}x{p}]", findings, file,
            )
        except Exception as e:  # noqa: BLE001
            findings.append(
                Finding(
                    CHECK, file, 1, "sharded_auction_assign",
                    f"eval_shape failed at bucket {n}x{p}: {e}",
                )
            )

    for label, sigs in signatures.items():
        if len(sigs) != calls[label]:
            findings.append(
                Finding(
                    CHECK, file, 1, label,
                    f"{calls[label]} lattice points produced "
                    f"{len(sigs)} distinct compile keys — the sharded "
                    "signature set must be exactly one per (bucket, "
                    "mesh shape)",
                )
            )

    # the mesh shape must DISCRIMINATE: a sharded signature colliding
    # with its single-chip twin would let one executable cache serve
    # both layouts (prewarm/retrace keys carry the mesh for this reason)
    n, p = LATTICE[0]
    if n % size == 0:
        snap = abstract_snapshot(byclass, limits, n=n, p=p)
        if retrace.signature(snap, (1, ff_off, 0)) in signatures[
            "greedy-sharded"
        ]:
            findings.append(
                Finding(
                    CHECK, file, 1, "mesh_signature",
                    "sharded compile key collides with the single-chip "
                    "key (mesh shape must be part of the signature)",
                )
            )


def check(root: str, package: str = "kubernetes_tpu") -> List[Finding]:
    """Run the full recompile-discipline suite.  Imports JAX; callers
    wanting an import-light lint use run_all instead."""
    byclass = _schema_contracts(root, package)
    findings: List[Finding] = []
    _check_encode(byclass, findings)
    _check_kernels(byclass, findings)
    _check_preemption_kernel(byclass, findings)
    _check_mesh_kernels(byclass, findings)
    _check_slice_kernels(byclass, findings)
    _check_partials_kernels(byclass, findings)
    _check_axis_transitions(byclass, findings)
    _check_gang_retry_closure(findings)
    findings.sort(key=lambda f: (f.file, f.line, f.message))
    return findings
