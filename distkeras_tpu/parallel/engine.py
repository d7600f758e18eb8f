"""The window engine: compiled replacement for the Trainer/Worker/PS loop.

Reference call stack being replaced (SURVEY.md §3.1): driver starts a PS
thread, ships pickled workers to Spark executors, each worker loops
``model.train_on_batch`` and every ``communication_window`` batches does a
socket ``commit``/``pull`` round-trip to the driver.

TPU-native shape: ONE jitted function per epoch —

    shard_map over the 'replica' mesh axis of:
        lax.scan over windows of:
            lax.scan over the window's minibatches:  local optax step
            algorithm.window_commit(...):            psum collective

The whole epoch is a single XLA program: no Python in the hot loop, no
host round-trips, the commit is an ICI allreduce fused into the step.
"""

from __future__ import annotations

import functools
import re
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distkeras_tpu import observability as obs
from distkeras_tpu.models.base import Model, ModelSpec
from distkeras_tpu.parallel.algorithms import Algorithm


@struct.dataclass
class ReplicaState:
    """Global training state. ``local``/``opt_state``/``extra`` carry a
    leading replica axis (sharded over the mesh); ``center`` is replicated —
    it is the PS's "center variable" of the reference, now mesh-invariant."""

    center: Any
    local: Any
    opt_state: Any
    extra: Any
    step: jnp.ndarray


def _ensure_varying(x, axis_name: str):
    """Mark ``x`` as varying over ``axis_name`` unless it already is."""
    if axis_name in jax.typeof(x).vma:
        return x
    return lax.pcast(x, (axis_name,), to="varying")


# a dotted lower-case name on an op's path: a ``jax.named_scope`` of the
# program's (no flax module, transform or file is named so)
_SCOPE_NAME = re.compile(r'[/("]([a-z]+(?:\.[a-z_]+)+)(?<!\.py)(?=[/")])')


def _compiled_text(fn, avals, **jit_kw) -> str:
    """The compiled HLO text of the jitted ``fn`` at ``avals`` with THIS
    program's metadata.  JAX's persistent compilation cache leaves metadata
    out of its key, so an executable cached before a ``jax.named_scope`` was
    added (by another commit that shares the cache directory) is served
    again with its old ``op_name``s, and every new scope reads empty with no
    error.  Where a scope of the lowered program is missing from the compiled
    text, the function is traced anew and compiled with the metadata in the
    key (the flag is the process's for the length of that one compile; only
    an ask with telemetry on reaches it); the instruction names are those of
    the executable that ran (the optimiser does not read metadata)."""
    lowered = fn.lower(*avals)
    text = lowered.compile().as_text()
    scopes = set(_SCOPE_NAME.findall(lowered.as_text(debug_info=True)))
    if all(name in text for name in scopes):
        return text
    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        # a new function object: the old one's lowering and executable are
        # cached in memory under it
        again = functools.wraps(fn.__wrapped__)(lambda *args: fn.__wrapped__(*args))
        return jax.jit(again, **jit_kw).lower(*avals).compile().as_text()
    finally:
        jax.config.update(key, before)


def make_minibatch_step(apply_fn: Callable, loss: Callable,
                        optimizer: optax.GradientTransformation,
                        with_rng: bool = False, hook=None) -> Callable:
    """One ``train_on_batch`` equivalent: value_and_grad + optax update.

    ``with_rng=True``: ``apply_fn`` is a train-mode forward taking a PRNG
    key (``ModelSpec.train_apply_fn``) and each scanned batch is
    ``(x, y, key)`` — the key rides the batch stream, NOT the carry, so
    state layouts (and checkpoint formats) are identical either way.

    ``hook`` (``ModelSpec.step_hook()``): the place for a leaf that the
    step, not the optimizer, updates.  The forward is ``hook.apply`` and
    hands back stats beside the output; after the optimizer's update
    ``hook.update(params, stats)`` moves its leaves, and the step's output
    is ``(loss, stats)`` instead of the loss.
    """
    def scoped_loss(out, labels):
        # step.loss / step.update (and step.commit in the window program) name
        # the step's own parts for the device account (obs.device_account)
        with jax.named_scope("step.loss"):
            return loss(out, labels)

    if hook is not None:
        if with_rng:
            raise ValueError("a step hook and a dropout key stream do not compose (v1)")

        def hooked_loss(params, batch):
            out, stats = hook.apply(params, batch[0])
            return scoped_loss(out, batch[1]), stats

        def hooked_step(carry, batch):
            params, opt_state = carry
            (loss_val, stats), grads = jax.value_and_grad(
                hooked_loss, has_aux=True)(params, batch)
            with jax.named_scope("step.update"):
                updates, opt_state = optimizer.update(grads, opt_state, params)
                params = hook.update(optax.apply_updates(params, updates), stats)
            return (params, opt_state), (loss_val, stats)

        return hooked_step
    if with_rng:
        def loss_of(params, batch):
            return scoped_loss(apply_fn(params, batch[0], batch[2]), batch[1])
    else:
        def loss_of(params, batch):
            return scoped_loss(apply_fn(params, batch[0]), batch[1])

    def step(carry, batch):
        params, opt_state = carry
        loss_val, grads = jax.value_and_grad(loss_of)(params, batch)
        with jax.named_scope("step.update"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return (params, opt_state), loss_val

    return step


def scan_epoch_fn(apply_fn: Callable, loss: Callable,
                  optimizer: optax.GradientTransformation,
                  with_rng: bool = False) -> Callable:
    """Single-device compiled epoch: lax.scan over [num_batches, bs, ...].

    Backs ``SingleTrainer`` — the reference's minimal path (SURVEY §3.2)
    with the per-row partition iterator replaced by one device transfer
    and one XLA program per epoch.  ``with_rng``: see
    :func:`make_minibatch_step`; the epoch then takes per-batch keys
    [num_batches, 2] as a fourth array.
    """
    mini = make_minibatch_step(apply_fn, loss, optimizer, with_rng=with_rng)

    if with_rng:
        def epoch(params, opt_state, xs, ys, keys):
            (params, opt_state), losses = lax.scan(
                mini, (params, opt_state), (xs, ys, keys))
            return params, opt_state, losses
    else:
        def epoch(params, opt_state, xs, ys):
            (params, opt_state), losses = lax.scan(mini, (params, opt_state), (xs, ys))
            return params, opt_state, losses

    return jax.jit(epoch, donate_argnums=(0, 1))


class WindowEngine:
    """Builds and runs the sharded window-training program for one
    (model spec, loss, optimizer, algorithm, mesh) combination."""

    def __init__(self, spec: ModelSpec, loss: Callable,
                 optimizer: optax.GradientTransformation, algorithm: Algorithm,
                 mesh: Mesh, axis_name: str = "replica", window: int = 1):
        spec.reject_silent_aux("WindowEngine")
        self.spec = spec
        self.loss = loss
        self.optimizer = optimizer
        self.algorithm = algorithm
        self.mesh = mesh
        self.axis_name = axis_name
        self.window = int(window)
        self.num_replicas = mesh.shape[axis_name]
        # dropout-bearing specs train through the rng-taking forward; the
        # per-batch keys ride the scanned data stream (state layout — and
        # therefore checkpoints — identical either way)
        self.needs_rng = spec.needs_rng
        self._apply = spec.train_apply_fn() if self.needs_rng else spec.apply_fn()
        # a leaf the step itself moves (the routed expert layer's selection
        # bias): its forward hands back stats, which the window program
        # sums and returns beside the losses
        self._hook = spec.step_hook()
        self._epoch_fns: Dict[int, Callable] = {1: self._build_epoch_fn()}

    # -- state ----------------------------------------------------------------
    def _state_specs(self) -> ReplicaState:
        return ReplicaState(
            center=P(),
            local=P(self.axis_name),
            opt_state=P(self.axis_name),
            extra=P(self.axis_name),
            step=P(),
        )

    def _state_shardings(self) -> ReplicaState:
        return jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec),
            self._state_specs(),
            is_leaf=lambda x: isinstance(x, P),
        )

    def init_state(self, model: Model, divergent_seeds: Optional[Sequence[int]] = None) -> ReplicaState:
        """Replicate the model into per-replica locals + a shared center.

        ``divergent_seeds`` gives each replica its own re-initialization
        (EnsembleTrainer's decorrelation; reference ``uniform_weights``).
        """
        r = self.num_replicas
        center = jax.tree.map(np.asarray, model.params)
        if divergent_seeds is not None:
            if len(divergent_seeds) != r:
                raise ValueError(f"need {r} seeds, got {len(divergent_seeds)}")
            locals_list = [
                jax.tree.map(np.asarray, self.spec.init_params(seed=s)) for s in divergent_seeds
            ]
        else:
            locals_list = [center] * r
        local = jax.tree.map(lambda *xs: np.stack(xs), *locals_list)
        opt0 = self.optimizer.init(model.params)
        opt_np = jax.tree.map(np.asarray, opt0)
        opt_state = jax.tree.map(lambda x: np.stack([x] * r), opt_np)
        extra0 = self.algorithm.init_extra(model.params)
        extra = jax.tree.map(lambda x: np.stack([np.asarray(x)] * r), extra0)
        state = ReplicaState(center=center, local=local, opt_state=opt_state,
                             extra=extra, step=np.zeros((), np.int32))
        return self.shard_state(state)

    def shard_state(self, state: ReplicaState) -> ReplicaState:
        """Place a (host or restored-from-checkpoint) state onto the mesh
        with this engine's shardings.

        Multi-process (``jax.distributed`` initialized, mesh spanning
        hosts): every process holds the same full host-side state and
        contributes just its addressable shards via
        ``make_array_from_callback`` — ``device_put`` cannot place onto
        non-addressable devices."""
        shardings = self._state_shardings()
        if jax.process_count() == 1:
            return jax.device_put(state, shardings)

        def put(subtree, sharding):
            # one sharding per ReplicaState FIELD (device_put broadcasts
            # prefix trees itself; make_array_from_callback does not)
            def leaf(l):
                host = np.asarray(l)
                return jax.make_array_from_callback(
                    host.shape, sharding, lambda idx, h=host: h[idx])

            return jax.tree.map(leaf, subtree)

        return ReplicaState(
            center=put(state.center, shardings.center),
            local=put(state.local, shardings.local),
            opt_state=put(state.opt_state, shardings.opt_state),
            extra=put(state.extra, shardings.extra),
            step=put(state.step, shardings.step),
        )

    # -- compiled epoch --------------------------------------------------------
    def _build_epoch_fn(self, reps: int = 1) -> Callable:
        """``reps > 1`` compiles ``reps`` passes over the same data into
        ONE program (outer lax.scan) — the steady-state measurement shape:
        per-dispatch host overhead amortizes across every epoch instead
        of dominating each one at toy sizes (what a dispatch costs the
        host at the benchmark's sizes is PERF.md's ``engine_host_ms``)."""
        algo = self.algorithm
        axis = self.axis_name
        needs_rng = self.needs_rng
        hook = self._hook
        mini = make_minibatch_step(self._apply, self.loss, self.optimizer,
                                   with_rng=needs_rng, hook=hook)

        def shard_fn(state: ReplicaState, xs, ys, keys):
            # per-shard views: strip the leading (sharded) replica axis
            local = jax.tree.map(lambda a: a[0], state.local)
            opt_state = jax.tree.map(lambda a: a[0], state.opt_state)
            extra = jax.tree.map(lambda a: a[0], state.extra)
            center = state.center

            def window_step(carry, window_batches):
                center, local, opt_state, extra = carry
                if needs_rng:
                    wx, wy, wk = window_batches
                    # same base key per batch everywhere, diverged per
                    # replica so the masks differ across workers
                    ridx = lax.axis_index(axis)
                    wk = jax.vmap(lambda kk: jax.random.fold_in(kk, ridx))(wk)
                    batches = (wx, wy, wk)
                else:
                    wx, wy = window_batches
                    batches = (wx, wy)
                (local, opt_state), losses = lax.scan(mini, (local, opt_state), batches)
                with jax.named_scope("step.commit"):
                    if hook is not None:
                        # the window's stats: summed over its steps and replicas
                        losses, stats = losses
                        stats = jax.tree.map(
                            lambda a: lax.psum(jnp.sum(a, axis=0), axis), stats)
                    center, local, extra = algo.window_commit(center, local, extra, axis)
                    # commit rules that reset local to the (mesh-invariant) center
                    # change the carry's varying-axes type; cast it back
                    local = jax.tree.map(lambda x: _ensure_varying(x, axis), local)
                    extra = jax.tree.map(lambda x: _ensure_varying(x, axis), extra)
                    mean_loss = lax.pmean(jnp.mean(losses), axis)
                if hook is not None:
                    return (center, local, opt_state, extra), (mean_loss, stats)
                return (center, local, opt_state, extra), mean_loss

            data = (xs, ys, keys) if needs_rng else (xs, ys)
            if reps == 1:
                (center, local, opt_state, extra), window_losses = lax.scan(
                    window_step, (center, local, opt_state, extra), data)
            else:
                def one_pass(carry, _):
                    carry, losses = lax.scan(window_step, carry, data)
                    return carry, losses

                (center, local, opt_state, extra), window_losses = lax.scan(
                    one_pass, (center, local, opt_state, extra), None, length=reps)
                # last pass's per-window losses
                window_losses = jax.tree.map(lambda a: a[-1], window_losses)
            num_steps = xs.shape[0] * xs.shape[1] * reps
            new_state = ReplicaState(
                center=center,
                local=jax.tree.map(lambda a: a[None], local),
                opt_state=jax.tree.map(lambda a: a[None], opt_state),
                extra=jax.tree.map(lambda a: a[None], extra),
                step=state.step + jnp.int32(num_steps),
            )
            return new_state, window_losses

        specs = self._state_specs()
        data_spec = P(None, None, axis)
        sharded = jax.shard_map(
            shard_fn,
            mesh=self.mesh,
            in_specs=(specs, data_spec, data_spec, P()),  # keys replicated
            out_specs=(specs, P()),   # P(): the losses, and a hook's stats beside them
        )
        return jax.jit(sharded, donate_argnums=(0,))

    def data_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(None, None, self.axis_name))

    def steady_state_rate(self, state: ReplicaState, xs: np.ndarray, ys: np.ndarray,
                          reps: int = 4, repeat: int = 3) -> float:
        """Measured samples/sec/chip with ``reps`` epochs over ``xs``/``ys``
        inside ONE compiled program — the number that reflects the chip,
        not the per-dispatch host overhead.  The engine's training state
        is copied per run (the epoch program donates its input), so the
        caller's ``state`` stays usable.  Median of ``repeat`` runs."""
        import time as _time

        self.spec.reject_rng_spec("steady_state_rate")
        fn = self._epoch_fns.get(reps)
        if fn is None:
            fn = self._build_epoch_fn(reps)
            self._epoch_fns[reps] = fn
        xs_d, ys_d = self._place_data(xs, ys)  # multi-process safe
        keys = self._place_keys(np.zeros(xs.shape[:2] + (2,), np.uint32))
        samples = reps * xs.shape[0] * xs.shape[1] * xs.shape[2]

        def fresh():
            return jax.tree.map(jnp.array, state)

        _, losses = fn(fresh(), xs_d, ys_d, keys)
        jax.block_until_ready(losses)  # compile + completion barrier
        rates = []
        for _ in range(repeat):
            s = fresh()
            t0 = _time.perf_counter()
            _, losses = fn(s, xs_d, ys_d, keys)
            jax.block_until_ready(losses)
            rates.append(samples / (_time.perf_counter() - t0))
        return sorted(rates)[len(rates) // 2] / self.num_replicas

    def run_epoch(self, state: ReplicaState, xs: np.ndarray, ys: np.ndarray,
                  keys: Optional[np.ndarray] = None):
        """xs/ys: [num_windows, window, global_batch, ...] host arrays;
        ``keys`` [num_windows, window, 2] uint32 per-batch dropout keys
        (required iff the spec ``needs_rng``).

        Returns (new_state, per-window mean losses as numpy).

        Telemetry (when ``distkeras_tpu.observability`` is enabled):
        dispatch-to-completion time per compiled epoch-chunk program
        (``engine_epoch_seconds`` — the ``np.asarray`` below blocks, so
        the interval IS the program's effective duration incl. dispatch),
        split by the leaf phases ``engine.place`` / ``engine.dispatch`` /
        ``engine.device_wait``.
        """
        telemetry = obs.enabled()
        t0 = time.perf_counter() if telemetry else 0.0
        with obs.span("engine.run_epoch", windows=int(np.shape(xs)[0]),
                      replicas=self.num_replicas):
            xs_d, ys_d = self._place_data(xs, ys)
            if keys is None:
                # any constant is a valid (unused) threefry key when the spec
                # has no rng need; a real run with needs_rng must pass keys
                if self.needs_rng:
                    raise ValueError("this engine's spec needs per-batch dropout "
                                     "keys; pass keys=[num_windows, window, 2]")
                keys = np.zeros(xs.shape[:2] + (2,), np.uint32)
            # leaf phases: the host's time between chunk programs (the
            # chunk's own transfer is engine.place inside place_data, on
            # whichever thread issues it)
            with obs.phase("engine.place", what="keys"):
                keys_d = self._place_keys(np.asarray(keys))
            if telemetry:
                # the program's compiled text, for whoever asks which scope
                # a device event ran under (obs.device_scopes): shapes only
                # are kept, the compile happens on asking and hits the cache
                fn, avals = self._epoch_fns[1], jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
                    (state, xs_d, ys_d, keys_d))
                obs.note_program(
                    "jit_shard_fn", lambda: _compiled_text(fn, avals, donate_argnums=(0,)))
            with obs.phase("engine.dispatch"):
                state, losses = self._epoch_fns[1](state, xs_d, ys_d, keys_d)
            with obs.phase("engine.device_wait"):
                if self._hook is not None:
                    losses, stats = losses
                losses = np.asarray(losses)
        if telemetry and self._hook is not None:
            self._hook.publish(jax.tree.map(np.asarray, stats))
        if telemetry:
            # identity as labels (ARCHITECTURE.md convention): a process
            # with several engines (elastic rebuilds) must not
            # merge differently-shaped programs into one histogram
            obs.histogram("engine_epoch_seconds", model=self.spec.name,
                          replicas=str(self.num_replicas)).observe(
                time.perf_counter() - t0)
        return state, losses

    def lower_epoch(self, state: ReplicaState, xs: np.ndarray, ys: np.ndarray):
        """The epoch program ``run_epoch`` would dispatch for these inputs,
        lowered but not run (``jax.stages.Lowered``) — for checking what
        was selected inside it: ``chip_smoke.py`` requires the attention
        in the program's text to be the Mosaic custom call."""
        xs_d, ys_d = self.place_data(xs, ys)
        keys_d = self._place_keys(np.zeros(xs.shape[:2] + (2,), np.uint32))
        return self._epoch_fns[1].lower(state, xs_d, ys_d, keys_d)

    def _place_keys(self, keys: np.ndarray):
        """Replicated placement for the per-batch key stream — a
        process-local array cannot enter a program spanning processes."""
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(
                NamedSharding(self.mesh, P()), keys)
        return jnp.asarray(keys)

    def place_data(self, xs, ys):
        """Host chunk -> mesh-sharded device arrays (asynchronous issue;
        public so trainers can double-buffer via ``prefetch_to_device``);
        in a multi-process run every process passes the same GLOBAL chunk
        and contributes the batch columns its devices own (exact parity
        with the single-process replica->rows assignment, which a
        contiguous dataset-level shard would not give).  Already-placed
        ``jax.Array`` inputs pass through untouched, so ``run_epoch``
        accepts either form."""
        if isinstance(xs, jax.Array) and isinstance(ys, jax.Array):
            return xs, ys
        sharding = self.data_sharding()
        with obs.phase("engine.place", what="data"):
            if jax.process_count() > 1:
                lo, hi = self._local_batch_range(xs.shape[2])
                return (jax.make_array_from_process_local_data(
                            sharding, xs[:, :, lo:hi]),
                        jax.make_array_from_process_local_data(
                            sharding, ys[:, :, lo:hi]))
            return jax.device_put(xs, sharding), jax.device_put(ys, sharding)

    _place_data = place_data  # backward-compatible alias

    def _local_batch_range(self, global_batch: int):
        """Global-batch column range owned by this process's devices (the
        replica axis shards the batch dim in mesh-device order)."""
        devs = list(self.mesh.devices.ravel())
        if global_batch % len(devs):
            # single-process device_put raises on this; fail identically
            # instead of silently dropping the trailing columns
            raise ValueError(
                f"global batch {global_batch} is not divisible by the "
                f"{len(devs)}-device mesh; pad or resize the batch")
        per = global_batch // len(devs)
        mine = [i for i, d in enumerate(devs)
                if d.process_index == jax.process_index()]
        if not mine:
            raise RuntimeError("this process owns no devices of the engine mesh")
        if mine != list(range(mine[0], mine[-1] + 1)):
            raise NotImplementedError(
                f"non-contiguous local device placement {mine} in the mesh; "
                "build the mesh from jax.devices() order")
        return mine[0] * per, (mine[-1] + 1) * per

    # -- results ---------------------------------------------------------------
    def center_model(self, state: ReplicaState) -> Model:
        """The trained center — reference ``parameter_server.get_model()``."""
        return Model(spec=self.spec, params=jax.tree.map(lambda x: jnp.asarray(x), state.center))

    def _gather_rows(self, subtree):
        """Compiled one-replica-row gather: a [R, ...]-leading sharded
        pytree -> R replicated row pytrees, one collective per row.

        Row-at-a-time keeps the PEAK extra device memory at O(one model
        copy) instead of replicating the full O(model x replicas) stack
        into every device's HBM — a state that only fits sharded must not
        OOM at exactly the checkpoint/ensemble moment the gather exists
        for.  SPMD caveat: this dispatches collectives, so in a
        multi-process run EVERY process must call it with the same
        state."""
        fn = getattr(self, "_row_gather_fn", None)
        if fn is None:
            fn = jax.jit(
                lambda t, i: jax.tree.map(lambda a: jnp.take(a, i, axis=0), t),
                out_shardings=NamedSharding(self.mesh, P()))
            self._row_gather_fn = fn  # fresh lambdas would defeat the jit cache
        return [fn(subtree, jnp.int32(i)) for i in range(self.num_replicas)]

    def gather_state(self, state: ReplicaState, to_host: bool = True) -> Optional[ReplicaState]:
        """Full HOST copy of the training state, gathered row-by-row.

        The sharded fields (``local``/``opt_state``/``extra``) are pulled
        one replica row per collective (see ``_gather_rows``); ``center``
        and ``step`` are already replicated and copy straight out.  This
        is what makes checkpointing and ``local_models`` work when
        replicas live on other hosts.

        ``to_host=False`` runs ONLY the collectives (every process must
        participate in them) and returns ``None`` without materializing
        anything in host RAM — the non-writer processes of a checkpoint
        save use this so an N-host run doesn't copy N-1 redundant full
        states per epoch."""
        rows = {name: self._gather_rows(getattr(state, name))
                for name in ("local", "opt_state", "extra")}
        if not to_host:
            return None
        stacked = {
            name: jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                               *field_rows)
            for name, field_rows in rows.items()
        }
        return ReplicaState(
            center=jax.tree.map(np.asarray, state.center),
            local=stacked["local"],
            opt_state=stacked["opt_state"],
            extra=stacked["extra"],
            step=np.asarray(state.step),
        )

    def local_models(self, state: ReplicaState) -> List[Model]:
        """All per-replica models (EnsembleTrainer's return value).

        Multi-process meshes gather the ``local`` field row-by-row (just
        the weights — not the 2-3x larger optimizer slots), so every
        process returns the identical full ensemble."""
        if jax.process_count() > 1:
            rows = self._gather_rows(state.local)
            return [Model(spec=self.spec,
                          params=jax.tree.map(jnp.asarray, row)) for row in rows]
        local_np = jax.tree.map(np.asarray, state.local)
        models = []
        for i in range(self.num_replicas):
            params = jax.tree.map(lambda a: jnp.asarray(a[i]), local_np)
            models.append(Model(spec=self.spec, params=params))
        return models

    def averaged_model(self, state: ReplicaState) -> Model:
        """Arithmetic mean of locals (AveragingTrainer, reference §2.2).

        The mean runs as a compiled reduction with a REPLICATED output, so
        it also works when the replicas live on other hosts."""
        mean_fn = getattr(self, "_mean_fn", None)
        if mean_fn is None:
            mean_fn = jax.jit(
                lambda local: jax.tree.map(lambda a: jnp.mean(a, axis=0), local),
                out_shardings=NamedSharding(self.mesh, P()))
            self._mean_fn = mean_fn  # fresh lambdas would defeat the jit cache
        return Model(spec=self.spec, params=mean_fn(state.local))
