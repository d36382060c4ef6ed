"""Rule-based logical-axis -> PartitionSpec resolution.

Model code never names mesh axes.  It annotates tensors with *logical* axes
(``"batch"``, ``"heads"``, ``"mlp"``, ``"expert"``, ``"einet_nodes"``, ...)
via :func:`constraint`, and parameter/batch placement is derived from the
leaf's *tree path* via :func:`tree_shardings` / :func:`batch_shardings`.  A
rule table -- installed with :func:`use_rules` -- maps each logical axis to a
mesh axis (or a tuple of mesh axes, or None for replicated).  Swapping the
table re-targets the whole model: single-pod vs multi-pod DP, FSDP on or
off, sequence parallelism on or off, with zero changes to model code.

Degradation contract (load-bearing for the tier-1 suite): every entry point
is a no-op when there are no rules in scope, no ambient mesh, or a 1-device
mesh -- so the single-device path has no distribution dependencies and jit
traces are byte-identical to an annotation-free model.

Resolution of one tensor dim:
  logical name -> rules[name] -> mesh axes; the axes are kept only if they
  all exist in the mesh, none was already used by an earlier dim of the same
  tensor, and the dim size divides evenly -- otherwise that dim degrades to
  replicated (never an error: rules are preferences, not requirements).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

Rules = Dict[str, Any]  # logical axis -> mesh axis | tuple of axes | None

_state = threading.local()


# ===========================================================================
# rule tables
# ===========================================================================
def default_rules(multi_pod: bool, fsdp: bool) -> Rules:
    """The production rule table.

    * ``batch``  -- data parallelism over ("pod", "data") / ("data",); the
      "pod" axis is the slow DCN axis, only DP reductions cross it.
    * ``seq`` / ``heads`` / ``mlp`` / ``vocab`` -- megatron-style tensor
      parallelism: activations carry the "model" axis on different dims at
      different points of the layer.
    * ``expert`` -- expert parallelism for MoE (a single axis name: the
      all-to-all needs one contiguous axis).
    * ``einet_nodes`` -- the EiNet layer-node axis (paper Eq. 5's L dim):
      einsum weights, EM statistics and leaf rows all shard over "model"
      along it, which is what makes the E-step psum move K x K blocks
      instead of full layers.
    * ``fsdp`` -- parameter sharding over the fast DP axis (ZeRO-3 style);
      None keeps parameters fully replicated over DP.
    """
    dp: Tuple[str, ...] = ("pod", "data") if multi_pod else ("data",)
    return {
        "batch": dp,
        "seq": "model",
        "heads": "model",
        "mlp": "model",
        "vocab": "model",
        "expert": "model",
        "expert_mlp": None,
        "einet_nodes": "model",
        "fsdp": ("data",) if fsdp else None,
    }


def serve_rules(multi_pod: bool = False) -> Rules:
    """Rule table for the batched inference engine (``repro.serve``): data
    parallelism over the micro-batch, layer-node sharding over "model", no
    FSDP (serving keeps parameters resident).  Degrades to a no-op on a
    single device like every other table."""
    return default_rules(multi_pod, fsdp=False)


@contextlib.contextmanager
def use_rules(rules: Rules):
    """Install ``rules`` for the dynamic extent of the block (re-entrant:
    the innermost table wins, the outer one is restored on exit)."""
    stack = getattr(_state, "stack", None)
    if stack is None:
        stack = _state.stack = []
    stack.append(dict(rules))
    try:
        yield rules
    finally:
        stack.pop()


def get_rules() -> Optional[Rules]:
    """The innermost active rule table, or None outside any use_rules."""
    stack = getattr(_state, "stack", None)
    return stack[-1] if stack else None


# ===========================================================================
# resolution
# ===========================================================================
def _mesh_axis_sizes(mesh) -> Dict[str, int]:
    return dict(mesh.shape)


def resolve_spec(
    axes: Sequence[Optional[str]],
    shape: Sequence[int],
    axis_sizes: Dict[str, int],
    rules: Rules,
) -> Optional[P]:
    """Pure resolution: logical axes + rules + mesh axis sizes -> spec.

    Returns None when nothing ended up sharded (caller skips the constraint).
    """
    used = set()
    entries = []
    for i, name in enumerate(axes):
        mesh_axes = rules.get(name) if name is not None else None
        if mesh_axes is None:
            entries.append(None)
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        else:
            mesh_axes = tuple(mesh_axes)
        prod = 1
        ok = True
        for ax in mesh_axes:
            if ax not in axis_sizes or ax in used:
                ok = False
                break
            prod *= axis_sizes[ax]
        dim = shape[i] if i < len(shape) else 0
        if not ok or prod <= 1 or dim <= 0 or dim % prod != 0:
            entries.append(None)
            continue
        used.update(mesh_axes)
        entries.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
    if not used:
        return None
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def _mesh_in_scope():
    """The mesh ``jax.set_mesh`` put in scope, or None when there is none
    or it holds one device."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.size > 1 else None


def constraint(x, axes: Sequence[Optional[str]]):
    """Pin ``x``'s layout to the resolved logical ``axes``.

    A no-op (returns ``x`` unchanged) without rules, without an ambient
    mesh, or on a 1-device mesh -- single-device callers pay nothing.
    """
    rules = get_rules()
    if rules is None:
        return x
    mesh = _mesh_in_scope()
    if mesh is None:
        return x
    spec = resolve_spec(axes, x.shape, _mesh_axis_sizes(mesh), rules)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ===========================================================================
# tree placement
# ===========================================================================
def _path_str(path) -> str:
    """jax key path -> "/nested/list/0/leaf" (stable across key types)."""
    parts = []
    for k in path:
        for attr in ("key", "idx", "name"):
            v = getattr(k, attr, None)
            if v is not None:
                parts.append(str(v))
                break
        else:
            parts.append(str(k))
    return "/" + "/".join(parts)


# (path suffix -> logical axes per dim), first match wins.  Matched with
# str.endswith / containment on the `_path_str` form, so the same table
# covers params, grads, EM statistics, and AdamW moment trees (whose leaves
# live under the same suffixes).
_PARAM_AXES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    # -- EiNet (phi: (D, K, R, |T|); einsum: (L, k_out, K, K); mixing: (M, C, k))
    ("/phi", ("einet_nodes", None, None, None)),
    ("/einsum/*", ("einet_nodes", None, None, None)),
    ("/mixing/*", ("einet_nodes", None, None)),
    ("/n_einsum/*", ("einet_nodes", None, None, None)),
    ("/n_mixing/*", ("einet_nodes", None, None)),
    ("/s_phi", ("einet_nodes", None, None, None)),
    ("/s_den", ("einet_nodes", None, None)),
    ("/class_prior", (None,)),
    # -- attention (stacked over periods: leading np dim)
    ("/wq", (None, "fsdp", "heads")),
    ("/wk", (None, "fsdp", "heads")),
    ("/wv", (None, "fsdp", "heads")),
    ("/wo", (None, "heads", "fsdp")),
    ("/bq", (None, "heads")),
    ("/bk", (None, "heads")),
    ("/bv", (None, "heads")),
    # -- MoE (router replicated: every token needs every expert's logit)
    ("/moe/router", (None, None, None)),
    ("/moe/wg", (None, "expert", "fsdp", None)),
    ("/moe/wu", (None, "expert", "fsdp", None)),
    ("/moe/wd", (None, "expert", None, "fsdp")),
    # -- dense FFN
    ("/mlp/wg", (None, "fsdp", "mlp")),
    ("/mlp/wu", (None, "fsdp", "mlp")),
    ("/mlp/wd", (None, "mlp", "fsdp")),
    # -- mamba
    ("/in_proj", (None, "fsdp", "mlp")),
    ("/conv_w", (None, None, "mlp")),
    ("/x_proj", (None, "mlp", None)),
    ("/dt_proj", (None, None, "mlp")),
    ("/dt_bias", (None, "mlp")),
    ("/a_log", (None, "mlp", None)),
    ("/d_skip", (None, "mlp")),
    ("/out_proj", (None, "mlp", "fsdp")),
    # -- xLSTM
    ("/up", (None, "fsdp", "mlp")),
    ("/wq_l", (None, None, "mlp")),
    ("/wk_l", (None, None, "mlp")),
    ("/wi", (None, "mlp", None)),
    ("/wf", (None, "mlp", None)),
    ("/down", (None, "mlp", "fsdp")),
    ("/wx", (None, "fsdp", "mlp")),
    ("/bx", (None, "mlp")),
    # -- embedding / unembedding
    ("/embed", ("vocab", "fsdp")),
    ("/head", ("fsdp", "vocab")),
)


def _axes_for_path(p: str, ndim: int) -> Optional[Tuple[Optional[str], ...]]:
    for suffix, axes in _PARAM_AXES:
        if suffix.endswith("/*"):
            stem = suffix[:-2]
            i = p.rfind("/")
            hit = i > 0 and p[:i].endswith(stem) and p[i + 1:].isdigit()
        else:
            hit = p.endswith(suffix)
        if hit:
            return axes if len(axes) == ndim else None
    return None


def _leaf_spec(path, x, axis_sizes: Dict[str, int], rules: Rules) -> P:
    shape = getattr(x, "shape", ())
    axes = _axes_for_path(_path_str(path), len(shape))
    if axes is None:
        return P()
    return resolve_spec(axes, shape, axis_sizes, rules) or P()


def _rules_for(mesh) -> Rules:
    rules = get_rules()
    if rules is None:
        rules = default_rules("pod" in _mesh_axis_sizes(mesh), fsdp=False)
    return rules


def tree_shardings(mesh, tree) -> Any:
    """NamedSharding per leaf, derived from the leaf's tree path.

    Covers parameter trees (LM and EiNet), gradient/EM-statistic trees, and
    optimizer-state trees (same path suffixes); unmatched leaves -- or
    leaves whose shape no longer lines up with the pattern, e.g. int8-
    quantized moments -- replicate.
    """
    rules = _rules_for(mesh)
    sizes = _mesh_axis_sizes(mesh)
    return jax.tree_util.tree_map_with_path(
        lambda path, x: NamedSharding(mesh, _leaf_spec(path, x, sizes, rules)),
        tree,
    )


def batch_shardings(mesh, batch) -> Any:
    """Shard every batch leaf's leading dim over the DP axes (replicate
    leaves whose leading dim does not divide)."""
    rules = _rules_for(mesh)
    sizes = _mesh_axis_sizes(mesh)

    def leaf(x):
        shape = getattr(x, "shape", ())
        axes = ("batch",) + (None,) * (len(shape) - 1) if shape else (None,)
        return NamedSharding(mesh, resolve_spec(axes, shape, sizes, rules) or P())

    return jax.tree_util.tree_map(leaf, batch)


def constrain_like_params(tree) -> Any:
    """Pin each leaf of ``tree`` to the layout its path would give a
    parameter: gradients and EM statistics realign to the weight sharding
    *before* the DP reduction, turning it into a reduce-scatter-shaped psum
    instead of moving replicated full tensors.  Identity without rules or
    a multi-device mesh."""
    rules = get_rules()
    if rules is None:
        return tree
    mesh = _mesh_in_scope()
    if mesh is None:
        return tree
    sizes = _mesh_axis_sizes(mesh)

    def leaf(path, x):
        spec = _leaf_spec(path, x, sizes, rules)
        if spec == P():
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(leaf, tree)
