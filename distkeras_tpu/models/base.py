"""Model abstraction: architecture registry + (spec, params) bundles.

Reference parity: the reference moved Keras models around as
``{architecture JSON, weight list}`` dicts (``distkeras/utils.py ::
serialize_keras_model``) and rebuilt+compiled them inside each Spark
executor (``distkeras/workers.py :: Worker.prepare_model``).  TPU-native
equivalent: an architecture is a *registry name + config dict* that builds
a Flax module deterministically, parameters are a pytree, and "compile"
is ``jax.jit`` of the step function — there is no per-worker rebuild
because SPMD replicas share one traced program.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu import utils

_MODEL_REGISTRY: Dict[str, Callable[..., Any]] = {}


def register_model(name: str):
    """Class decorator registering a Flax module under an architecture name."""

    def wrap(cls):
        _MODEL_REGISTRY[name] = cls
        cls.architecture_name = name
        return cls

    return wrap


class StepHook(NamedTuple):
    """What a training step needs beyond ``apply_fn`` for an architecture
    whose step, not its optimizer, moves a leaf (``ModelSpec.step_hook``).

    ``apply(params, x) -> (out, stats)``; ``update(params, stats) -> params``
    after the optimizer's update; ``publish(stats)`` on the host with the
    window program's summed stats, when telemetry is on."""

    apply: Callable
    update: Callable
    publish: Callable


def build_module(name: str, config: Dict[str, Any]):
    try:
        cls = _MODEL_REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown architecture {name!r}; known: {sorted(_MODEL_REGISTRY)}") from None
    return cls(**config)


def sparse_param_names(spec: "ModelSpec") -> Tuple[str, ...]:
    """Param-path leaf names this architecture declares as row-sparse
    ``[rows, dim]`` embedding tables (``sparse_param_names`` on the
    registered module class; empty for everything else).  This is the
    EmbeddingTable metadata the async trainers thread into the PS stack
    (ISSUE 9)."""
    cls = _MODEL_REGISTRY.get(spec.name)
    return tuple(getattr(cls, "sparse_param_names", ()) or ())


def sparse_leaf_indices(spec: "ModelSpec", params: Any) -> Tuple[int, ...]:
    """Flat-leaf indices (``jax.tree.flatten`` order — the PS template
    order) of the spec's declared sparse embedding tables: leaves whose
    param path ends in one of :func:`sparse_param_names` and that are
    2-D.  Empty when the architecture declares none."""
    names = set(sparse_param_names(spec))
    if not names:
        return ()
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for idx, (path, leaf) in enumerate(flat):
        last = path[-1] if path else None
        key = getattr(last, "key", getattr(last, "name", None))
        if key in names and getattr(leaf, "ndim", 0) == 2:
            out.append(idx)
    return tuple(out)


def sparse_table_fields(spec: "ModelSpec", params: Any):
    """Per-table input-column declaration for MULTI-VOCABULARY sparse
    architectures (ISSUE 15): which columns of the int-id feature matrix
    feed each sparse embedding table.

    The registered module class declares ``sparse_field_map`` — a dict
    mapping a MODULE PATH SEGMENT (e.g. ``"table_1"``, the flax
    submodule name that owns the table param) to the tuple of feature
    columns indexing that table.  Returns the column tuples aligned with
    :func:`sparse_leaf_indices` order, or ``None`` when the architecture
    declares no map — the single-vocabulary contract, where every table
    is indexed by EVERY column and all tables must share one row count
    (the async trainers enforce that reduction).

    Raises when a map exists but does not cover every sparse leaf: a
    silently-defaulted table would send another vocabulary's ids."""
    cls = _MODEL_REGISTRY.get(spec.name)
    fmap = getattr(cls, "sparse_field_map", None)
    if not fmap:
        return None
    names = set(sparse_param_names(spec))
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        last = path[-1] if path else None
        key = getattr(last, "key", getattr(last, "name", None))
        if key not in names or getattr(leaf, "ndim", 0) != 2:
            continue
        segs = [getattr(p, "key", getattr(p, "name", None)) for p in path]
        owner = next((s for s in segs if s in fmap), None)
        if owner is None:
            raise ValueError(
                f"architecture {spec.name!r} declares sparse_field_map "
                f"{sorted(fmap)} but sparse leaf at {segs} matches no "
                f"entry — every sparse table needs its column declaration")
        out.append(tuple(int(c) for c in fmap[owner]))
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """Declarative architecture record: registry name + config + input shape.

    ``input_shape`` excludes the batch dimension (Keras convention).
    """

    name: str
    config: Dict[str, Any]
    input_shape: Tuple[int, ...]
    input_dtype: str = "float32"

    def __post_init__(self):
        # canonicalize so a JSON round-trip (tuples -> lists) compares equal;
        # recurses through dicts too (sequential's layer dicts nest configs)
        def canon(v):
            if isinstance(v, (list, tuple)):
                return tuple(canon(x) for x in v)
            if isinstance(v, dict):
                return {k: canon(x) for k, x in v.items()}
            return v

        object.__setattr__(self, "config", {k: canon(v) for k, v in self.config.items()})
        object.__setattr__(self, "input_shape", tuple(self.input_shape))

    def build(self):
        return build_module(self.name, self.config)

    def init_params(self, seed: int = 0) -> Any:
        module = self.build()
        dummy = jnp.zeros((1,) + tuple(self.input_shape), dtype=self.input_dtype)
        variables = module.init(jax.random.PRNGKey(seed), dummy)
        return variables["params"]

    def apply_fn(self) -> Callable[[Any, jnp.ndarray], jnp.ndarray]:
        module = self.build()

        def apply(params: Any, x: jnp.ndarray) -> jnp.ndarray:
            return module.apply({"params": params}, x)

        return apply

    @property
    def needs_rng(self) -> bool:
        """True when training this architecture needs a PRNG key per step
        (currently: sequential stacks containing active dropout layers).
        Drives the trainers' key plumbing; paths without it must refuse
        such specs (``reject_rng_spec``) rather than silently train with
        dropout off."""
        if self.name != "sequential":
            return False
        return any(l.get("kind") == "dropout" and float(l.get("rate", 0)) > 0
                   for l in self.config.get("layers", ()))

    def reject_rng_spec(self, where: str) -> None:
        if self.needs_rng:
            raise ValueError(
                f"{where} has no PRNG plumbing (v1) and would silently train "
                "with dropout disabled; remove the dropout layers or use "
                "SingleTrainer / the sync distributed trainer family")

    def train_apply_fn(self) -> Callable[[Any, jnp.ndarray, Any], jnp.ndarray]:
        """Training-mode forward ``(params, x, rng) -> out``.

        For specs with ``needs_rng`` the key feeds the dropout rng stream
        and ``train=True`` activates the stochastic layers; otherwise the
        rng is ignored and this is exactly ``apply_fn``."""
        if not self.needs_rng:
            plain = self.apply_fn()
            return lambda params, x, rng: plain(params, x)
        module = self.build()

        def apply(params: Any, x: jnp.ndarray, rng) -> jnp.ndarray:
            return module.apply({"params": params}, x, train=True,
                                rngs={"dropout": rng})

        return apply

    def sown_collections(self) -> Tuple[str, ...]:
        """The collections a forward of this spec sows into, as its
        registered class declares them (``sown_collections(config)``;
        none declared = none sown)."""
        declare = getattr(_MODEL_REGISTRY.get(self.name), "sown_collections", None)
        return tuple(declare(self.config)) if declare else ()

    def step_hook(self):
        """``None``, or the :class:`StepHook` of an architecture whose
        training step itself moves a leaf (``transformer_lm`` with the
        routed expert layer: the selection bias, from the assignment counts
        the forward sows)."""
        make = getattr(_MODEL_REGISTRY.get(self.name), "step_hook", None)
        return make(self) if make else None

    def reject_step_hook(self, where: str) -> None:
        """Raise where a step builder has no place for such a leaf: run
        through a plain ``apply_fn`` step it would never move."""
        if self.step_hook() is not None:
            raise ValueError(
                f"{where} has no place for a leaf the step itself moves (the "
                "routed expert layer's selection bias); train this spec "
                "through a synchronous distributed trainer (ADAG and its "
                "siblings: parallel/engine.py::WindowEngine)")

    def reject_silent_aux(self, where: str) -> None:
        """Raise if training this spec through a plain ``apply_fn`` step
        would silently drop a sown LOSS term (``sow`` into an immutable
        collection is a no-op): the Switch layer's load-balance loss.  The
        question is what the spec sows, not whether it has experts: the
        sigmoid-routed layer balances by a bias and sows counts only."""
        if "aux_loss" in self.sown_collections():
            raise ValueError(
                f"{where} would silently drop the MoE load-balance aux losses "
                "(sow into an immutable collection is a no-op); train MoE "
                "models with parallel/moe.py :: make_moe_train_step / "
                "make_moe_lm_train_step")

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name,
            "config": dict(self.config),
            "input_shape": list(self.input_shape),
            "input_dtype": self.input_dtype,
        }

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "ModelSpec":
        return ModelSpec(
            name=d["name"],
            config=dict(d["config"]),
            input_shape=tuple(d["input_shape"]),
            input_dtype=d.get("input_dtype", "float32"),
        )


@dataclasses.dataclass
class Model:
    """A trained (or initialized) model: spec + parameter pytree.

    This is what trainers return — the analogue of the Keras model object
    the reference's ``Trainer.train`` handed back.
    """

    spec: ModelSpec
    params: Any

    @staticmethod
    def init(spec: ModelSpec, seed: int = 0) -> "Model":
        return Model(spec=spec, params=spec.init_params(seed))

    def _jitted_apply(self):
        # cached per instance: spec.apply_fn() returns a fresh closure each
        # call, which would defeat jax's jit cache and recompile every time
        cached = getattr(self, "_apply_cache", None)
        if cached is None:
            cached = jax.jit(self.spec.apply_fn())
            object.__setattr__(self, "_apply_cache", cached)
        return cached

    def apply(self, x: jnp.ndarray) -> jnp.ndarray:
        return self._jitted_apply()(self.params, x)

    def predict(self, x: np.ndarray, batch_size: int = 512) -> np.ndarray:
        """Batched jit'd inference over a host array (see also ModelPredictor)."""
        apply = self._jitted_apply()
        outs = []
        for i in range(0, len(x), batch_size):
            outs.append(np.asarray(apply(self.params, jnp.asarray(x[i : i + batch_size]))))
        return np.concatenate(outs, axis=0) if outs else np.zeros((0,))

    def serialize(self) -> bytes:
        return utils.serialize_model(self.spec.to_dict(), self.params)

    @staticmethod
    def deserialize(blob: bytes) -> "Model":
        arch, weights = utils.deserialize_model(blob)
        spec = ModelSpec.from_dict(arch)
        template = spec.init_params(seed=0)
        _, treedef = jax.tree.flatten(template)
        params = utils.unflatten_weights(treedef, weights)
        return Model(spec=spec, params=params)

    def copy(self) -> "Model":
        return Model(spec=self.spec, params=jax.tree.map(jnp.array, self.params))

    def summary(self) -> str:
        """Keras ``model.summary()`` parity: per-module parameter table.

        Groups leaves by top-level param-tree key (one row per layer/block),
        with shapes for single-leaf modules and totals throughout.
        """
        rows = []
        total = total_bytes = 0
        for name, sub in self.params.items():
            leaves = jax.tree.leaves(sub)
            n = sum(int(l.size) for l in leaves)
            nbytes = sum(int(l.size) * l.dtype.itemsize for l in leaves)
            shape = str(tuple(leaves[0].shape)) if len(leaves) == 1 else f"{len(leaves)} tensors"
            rows.append((name, shape, n))
            total += n
            total_bytes += nbytes
        name_w = max([5] + [len(r[0]) for r in rows])   # >= len("layer")
        shape_w = max([5] + [len(r[1]) for r in rows])  # >= len("shape")
        lines = [f'Model "{self.spec.name}"  (input {self.spec.input_shape}, '
                 f'{self.spec.input_dtype})',
                 f"{'layer':<{name_w}}  {'shape':<{shape_w}}  params"]
        lines.append("-" * len(lines[-1]))
        for name, shape, n in rows:
            lines.append(f"{name:<{name_w}}  {shape:<{shape_w}}  {n:,}")
        lines.append("-" * len(lines[1]))
        lines.append(f"total: {total:,} params  ({total_bytes / 1e6:.2f} MB)")
        return "\n".join(lines)
