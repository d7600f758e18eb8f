"""Measurement matrix runner (``BASELINE.json`` configs 1-5).

Runs each config end to end — load data, train, evaluate after every
epoch — and reports samples/sec/chip plus wall-clock-to-target-accuracy,
the two halves of the headline metric.  One JSON line per config; a
summary table at the end; ``--out FILE`` also writes the records there.
A run of this matrix is not the repository's account of speed: that is
``benchmark/run.py``'s cells and ``PERF_LEDGER.jsonl`` (PERF.md).

Offline environments run on the loaders' deterministic synthetic
stand-ins (flagged in every record); drop real ``mnist.npz`` /
``cifar10.npz`` / ``cifar100.npz`` into a cache dir (see
``data/loaders.py``) to measure the real thing.

Usage:
    distkeras-baseline --config all --epochs-cap 10
    distkeras-baseline --config 2 --cpu 8        # simulate an 8-chip slice
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Any, Dict, List, Optional


def _evaluate(model, test_ds) -> float:
    from distkeras_tpu.data.transformers import LabelIndexTransformer
    from distkeras_tpu.evaluators import AccuracyEvaluator
    from distkeras_tpu.predictors import ModelPredictor

    scored = ModelPredictor(model, features_col="features").predict(test_ds)
    scored = LabelIndexTransformer(scored["label"].shape[-1]).transform(scored)
    return AccuracyEvaluator(prediction_col="prediction_index",
                             label_col="label_index").evaluate(scored)


def _steady_rate(trainer, train_ds, reps: int = 3, max_windows: int = 64) -> float:
    """In-program steady-state samples/sec/chip: the
    multi-epoch program amortizes per-dispatch host overhead, so this
    column reflects chip throughput — unlike the wall columns, which also
    bill host feeding and one dispatch per epoch."""
    import time as _time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distkeras_tpu.trainers import DistributedTrainer

    cols = [trainer.features_col, trainer.label_col]
    if isinstance(trainer, DistributedTrainer):
        window = trainer.communication_window
        global_batch = trainer.batch_size * trainer.num_workers
        chunk = next(iter(train_ds.chunked_epoch(
            global_batch, cols, window=window, chunk_windows=max_windows)))
        engine = trainer.engine
        state = engine.init_state(trainer.model)
        return engine.steady_state_rate(
            state, chunk[trainer.features_col], chunk[trainer.label_col], reps=reps)

    # SingleTrainer: an outer scan
    # over reps of the inner per-batch scan, one compiled program.  Reject
    # dropout-bearing specs like the engine path does: silently timing the
    # eval-mode forward would overstate the steady rate
    trainer.model.spec.reject_rng_spec("_steady_rate")
    from distkeras_tpu.parallel.engine import make_minibatch_step

    chunk = next(iter(train_ds.chunked_epoch(
        trainer.batch_size, cols, window=1, chunk_windows=max_windows * 4)))
    xs = jnp.asarray(chunk[trainer.features_col].squeeze(1))
    ys = jnp.asarray(chunk[trainer.label_col].squeeze(1))
    mini = make_minibatch_step(trainer.model.spec.apply_fn(), trainer.loss,
                               trainer.optimizer)

    @jax.jit
    def multi(params, opt_state, xs, ys):
        def one_pass(carry, _):
            carry, losses = jax.lax.scan(mini, carry, (xs, ys))
            return carry, losses[-1]

        (params, opt_state), last = jax.lax.scan(
            one_pass, (params, opt_state), None, length=reps)
        return params, opt_state, last

    params = jax.tree.map(jnp.array, trainer.model.params)
    opt_state = trainer.optimizer.init(params)
    _, _, last = multi(params, opt_state, xs, ys)
    np.asarray(last)
    samples = reps * xs.shape[0] * xs.shape[1]
    rates = []
    for _ in range(3):
        t0 = _time.perf_counter()
        _, _, last = multi(params, opt_state, xs, ys)
        np.asarray(last)
        rates.append(samples / (_time.perf_counter() - t0))
    return sorted(rates)[len(rates) // 2]


def run_config(num: int, epochs_cap: int, batch_size: Optional[int] = None,
               synthetic_target: Optional[float] = None) -> Dict[str, Any]:
    """Train one BASELINE config to its accuracy target (or the epoch cap);
    returns the metric record."""
    import jax

    from distkeras_tpu import (ADAG, AEASGD, DOWNPOUR, DynSGD, SingleTrainer)
    from distkeras_tpu.data.loaders import load_cifar10, load_cifar100, load_mnist
    from distkeras_tpu.models.cnn import cifar_cnn_spec, mnist_cnn_spec
    from distkeras_tpu.models.mlp import mnist_mlp_spec
    from distkeras_tpu.models.resnet import resnet20_spec

    # (name, trainer class, trainer kwargs, spec, loader,
    #  real-data target, synthetic target).  Synthetic targets are
    # calibrated per shape so every config needs multiple epochs of REAL
    # training: the CIFAR-10 stand-in runs at signal amplitude 3.5 (at 7.0
    # the CNN configs hit 0.99 in 2 epochs, defeating wall-to-target; at
    # 3.5 / target 0.90 they cross around epoch 5), and 100-way
    # classification plateaus near 0.73 on the amplitude-7.0 generator
    # (bar 0.70, crossed around epoch 14).
    configs = {
        1: ("SingleTrainer MLP/MNIST", SingleTrainer, {},
            mnist_mlp_spec(), lambda: load_mnist(flatten=True), 0.97, 0.95),
        2: ("ADAG CNN/MNIST", ADAG, {"communication_window": 4},
            mnist_cnn_spec(), lambda: load_mnist(), 0.99, 0.95),
        3: ("AEASGD CNN/CIFAR-10", AEASGD, {"communication_window": 8, "rho": 1.0},
            cifar_cnn_spec(), lambda: load_cifar10(), 0.70, 0.90),
        4: ("DOWNPOUR CNN/CIFAR-10", DOWNPOUR, {"communication_window": 4},
            cifar_cnn_spec(), lambda: load_cifar10(), 0.70, 0.90),
        5: ("DynSGD ResNet-20/CIFAR-100", DynSGD, {"communication_window": 4},
            resnet20_spec(num_outputs=100), lambda: load_cifar100(), 0.40, 0.70),
    }
    name, cls, kwargs, spec, loader, real_target, synth_target = configs[num]
    train_ds, test_ds, info = loader()
    if synthetic_target is not None:
        synth_target = synthetic_target
    target = synth_target if info["synthetic"] else real_target
    bs = batch_size or (64 if num >= 3 else 128)
    lr = 0.05 if num != 5 else 0.02

    trainer = cls(spec, loss="categorical_crossentropy", worker_optimizer="sgd",
                  learning_rate=lr, batch_size=bs, num_epoch=1, seed=0, **kwargs)

    samples_per_epoch = len(train_ds)
    accs: List[float] = []
    epoch_walls: List[float] = []  # per-epoch train+eval wall: a
    # single-shot wall column swings from run to run; the per-epoch
    # spread makes the noise visible and the median de-noises the wall
    t0 = time.perf_counter()
    t_target = None
    for epoch in range(epochs_cap):
        # distinct shuffle order per outer epoch: each train() call runs its
        # internal epoch 0, whose shuffle seed is trainer.seed + 0
        trainer.seed = epoch
        t_ep = time.perf_counter()
        trainer.train(train_ds, shuffle=True)
        acc = float(_evaluate(trainer.model, test_ds))
        epoch_walls.append(time.perf_counter() - t_ep)
        accs.append(round(acc, 4))
        if t_target is None and acc >= target:
            t_target = time.perf_counter() - t0
            break
    wall = time.perf_counter() - t0
    # one extra epoch AFTER the target: the trainer's epoch program is
    # cached across train() calls (SingleTrainer._epoch_fn / the engine on
    # DistributedTrainer), so this record is the steady-state rate
    trainer.seed = epochs_cap
    trainer.train(train_ds, shuffle=True)
    # chips actually engaged by this trainer (SingleTrainer=1, mesh trainers
    # = replica count) — NOT jax.device_count()
    n_chips = trainer.metrics[-1]["chips"] if trainer.metrics else jax.device_count()
    epochs_run = len(accs)
    # the first epoch pays compilation; the median of the REMAINING epochs
    # is the de-noised per-epoch wall (falls back to all epochs when only
    # one ran).  spread = (max-min)/median over the same set.
    import statistics

    steady_walls = epoch_walls[1:] or epoch_walls
    if steady_walls:
        ep_median = statistics.median(steady_walls)
        ep_spread = ((max(steady_walls) - min(steady_walls)) / ep_median
                     if ep_median else 0.0)
    else:  # epochs_cap = 0: degenerate but must not crash
        ep_median = ep_spread = 0.0
    return {
        "config": num,
        "name": name,
        "data": "synthetic" if info["synthetic"] else "real",
        "chips": n_chips,
        "platform": jax.default_backend(),
        "batch_size": bs,
        "epochs_run": epochs_run,
        "accuracy": accs,
        "target": target,
        "target_reached": t_target is not None,
        "wall_to_target_s": round(t_target, 2) if t_target is not None else None,
        # single-shot wall above is tenancy-exposed; these qualify it:
        "epoch_walls_s": [round(w, 2) for w in epoch_walls],
        "epoch_wall_median_s": round(ep_median, 2),
        "epoch_wall_spread": round(ep_spread, 3),
        "wall_to_target_denoised_s": (
            round(epoch_walls[0] + ep_median * (epochs_run - 1), 2)
            if t_target is not None else None),
        # wall-inclusive rate (compile + train + eval — the user experience)
        "samples_per_sec_per_chip_wall": round(
            epochs_run * samples_per_epoch / wall / n_chips, 1),
        # best per-epoch rate from the trainer's own metrics — still billed
        # for host feeding + one dispatch per epoch
        "samples_per_sec_per_chip_train": max(
            (m["samples_per_sec_per_chip"] for m in trainer.metrics), default=None),
        # in-program multi-epoch rate (see _steady_rate): wall-timed over
        # one compiled program, so it still holds one dispatch's host time
        "samples_per_sec_per_chip_steady": round(_steady_rate(trainer, train_ds), 1),
        "final_loss": round(trainer.history[-1], 4) if trainer.history else None,
    }


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description="BASELINE.json config matrix runner")
    parser.add_argument("--config", default="all",
                        help="1-5 or 'all'")
    parser.add_argument("--cpu", type=int, default=0,
                        help="simulate this many CPU devices instead of real chips")
    # default cap sized for the HARDEST config on the synthetics
    # (config 5 crosses its 0.70 bar around epoch 14)
    parser.add_argument("--epochs-cap", type=int, default=18)
    parser.add_argument("--batch-size", type=int, default=None)
    parser.add_argument("--out", default=None, help="write records to this JSON file")
    args = parser.parse_args(argv)

    from distkeras_tpu.platform import select_platform

    select_platform(args.cpu)

    nums = [1, 2, 3, 4, 5] if args.config == "all" else [int(args.config)]
    records = []
    for n in nums:
        rec = run_config(n, epochs_cap=args.epochs_cap, batch_size=args.batch_size)
        records.append(rec)
        print(json.dumps(rec), flush=True)

    ok = all(r["target_reached"] for r in records)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=2)
    if not ok:
        print("WARNING: some configs missed their accuracy target", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
