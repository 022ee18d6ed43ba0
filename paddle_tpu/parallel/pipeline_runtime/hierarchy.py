"""DCN x ICI two-level meshes: the grad-sync layout the linter asks for.

PR 16's `dcn-allreduce-not-hierarchical` linter fires when a grad-sync
all-reduce spans a dcn-tagged axis together with >1 ici-tagged device —
pricing the saving of the two-level decomposition (reduce-scatter over
ICI, all-reduce of the 1/ici shard over DCN). This module is the layout
side that REALIZES it: sharding each parameter over the ici-tagged data
axis (ZeRO style) makes GSPMD emit exactly that decomposition — psum of a
sharded value lowers to reduce-scatter on the shard axis plus all-reduce
of the shard on the rest — so the linter event stream decomposes too and
the diagnostic goes quiet.

`dcn_crossing_collective_bytes` is the trust-but-verify half: it parses
`replica_groups` out of OPTIMIZED HLO and prices the bytes that actually
cross the dcn boundary, so a test can hold the realized DCN traffic
against the linter's post-decomposition story instead of taking the
sharding annotations on faith.
"""

import re

from paddle_tpu.utils.hlo import _shape_bytes, collective_lines, \
    opt_hlo_shapes

__all__ = ["hierarchical_param_axis", "dcn_crossing_collective_bytes"]


def hierarchical_param_axis(axis_names, axis_tags, data_axes):
    """The axis to shard parameters over so grad-sync decomposes
    hierarchically: the ici-tagged member of the feed-sharded (data)
    axes, and only when a dcn-tagged axis exists to decompose against.
    Returns None when the mesh is single-level (plain replicated layout
    is already optimal) or no ici data axis exists."""
    tags = dict(axis_tags or {})
    if not any(tags.get(a) == "dcn" for a in axis_names):
        return None
    for a in axis_names:
        if a in set(data_axes) and tags.get(a, "ici") == "ici":
            return a
    return None


# replica_groups={{0,2},{1,3}}
_GROUPS_EXPLICIT = re.compile(
    r"replica_groups=\{(\{[0-9, ]*\}(?:,\{[0-9, ]*\})*)\}")
# source_target_pairs={{0,2},{2,0}}   (collective-permute edges)
_PERMUTE_PAIRS = re.compile(
    r"source_target_pairs=\{(\{[0-9, ]*\}(?:,\{[0-9, ]*\})*)\}")
# replica_groups=[2,4]<=[2,2,2]T(2,1,0)   (iota form)
_GROUPS_IOTA = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?")


def _parse_replica_groups(line):
    """Device-id groups of one collective line, or None if the line has
    no parseable replica_groups (callers treat that conservatively)."""
    m = _GROUPS_EXPLICIT.search(line) or _PERMUTE_PAIRS.search(line)
    if m:
        # a permute's {src,dst} edge is a 2-member group for crossing
        # purposes ({d,d} self-edges are single-device, never crossing)
        return [
            sorted({int(x) for x in grp.split(",") if x.strip()})
            for grp in re.findall(r"\{([0-9, ]*)\}", m.group(1))
        ]
    m = _GROUPS_IOTA.search(line)
    if m:
        rows, cols = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        n = 1
        for d in dims:
            n *= d
        if rows * cols != n:
            return None
        ids = list(range(n))
        if m.group(4):
            # iota reshaped to `dims`, transposed by the permutation,
            # flattened row-major
            perm = [int(x) for x in m.group(4).split(",")]
            strides = [1] * len(dims)
            for i in range(len(dims) - 2, -1, -1):
                strides[i] = strides[i + 1] * dims[i + 1]
            tdims = [dims[p] for p in perm]
            tstrides = [strides[p] for p in perm]
            ids = []
            idx = [0] * len(tdims)
            for _ in range(n):
                ids.append(sum(i * s for i, s in zip(idx, tstrides)))
                for ax in range(len(tdims) - 1, -1, -1):
                    idx[ax] += 1
                    if idx[ax] < tdims[ax]:
                        break
                    idx[ax] = 0
        return [ids[r * cols:(r + 1) * cols] for r in range(rows)]
    return None


def dcn_crossing_collective_bytes(opt_text, mesh_shape, axis_names,
                                  axis_tags):
    """Per-device bytes moved by collectives whose replica groups span a
    dcn-tagged mesh coordinate, from optimized HLO. Device ids are the
    row-major mesh enumeration (jax default for a host-platform mesh).
    A line with no parseable replica_groups counts as crossing — the
    report must never undercount DCN traffic. Returns
    {"crossing_bytes", "local_bytes", "collectives": [...]}."""
    tags = dict(axis_tags or {})
    dcn_pos = [i for i, a in enumerate(axis_names)
               if tags.get(a) == "dcn"]
    strides = [1] * len(mesh_shape)
    for i in range(len(mesh_shape) - 2, -1, -1):
        strides[i] = strides[i + 1] * mesh_shape[i + 1]

    def dcn_coord(dev):
        return tuple(dev // strides[p] % mesh_shape[p] for p in dcn_pos)

    crossing = 0
    local = 0
    rows = []
    for kind, line in collective_lines(opt_text):
        line_bytes = 0
        for shape, dt in opt_hlo_shapes(line):
            line_bytes = max(line_bytes, _shape_bytes(shape, dt))
        groups = _parse_replica_groups(line)
        if groups is None:
            crosses = True
        else:
            crosses = any(
                len({dcn_coord(d) for d in grp}) > 1 for grp in grps
            ) if (grps := [g for g in groups if g]) else False
        if crosses:
            crossing += line_bytes
        else:
            local += line_bytes
        rows.append({
            "kind": kind,
            "bytes": line_bytes,
            "crosses_dcn": bool(crosses),
            "groups": groups[:4] if groups else None,
        })
    return {
        "crossing_bytes": crossing,
        "local_bytes": local,
        "collectives": rows,
    }
