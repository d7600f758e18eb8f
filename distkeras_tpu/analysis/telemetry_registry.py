"""Canonical telemetry-name registry (the spec for pass 4).

Every metric and span name the project may emit, in one place.  The
telemetry pass collects name string literals from both Python and C++
sources — call sites of ``obs.counter``/``gauge``/``histogram`` and
``span``/``start_span``/``record_span``, plus every namespace-shaped
literal (``ps_*``, ``ps.*``, ``worker.*``, ``health.*``) anywhere in the
tree — and fails on any name not listed here.  A typo'd name today
creates a silently-missing series that ``fleet_report`` coverage cannot
distinguish from "telemetry off"; against this registry it is a failed
test instead.

Adding a metric means adding its name here FIRST — the registry is the
reviewable diff of the telemetry namespace, the same way
``lock_manifest.LOCK_ORDER`` is for lock nesting.
"""

from __future__ import annotations

#: Prometheus-style counters/gauges/histograms (snake_case) and dotted
#: span/series names, grouped by plane.
TELEMETRY_NAMES = frozenset({
    # -- hub counters/gauges/histograms (both hub implementations emit
    #    these; runtime/native.py maps the C++ stat keys onto them) ------------
    "ps_commits_total", "ps_pulls_total",
    "ps_commit_bytes_total", "ps_pull_bytes_total",
    # client: prefetched pull replies claimed by land_weights, beside the
    # window program (not by the commit's guard, not by wait_weights)
    "ps_pulls_landed_early_total",
    # client: dense commits that left leaf by leaf, no frame packed
    # (FlatFrameCodec.send_streamed); int8 and row-sparse ones do not count
    "ps_commits_streamed_total",
    "ps_fenced_commits_total", "ps_idle_evictions_total",
    "ps_live_workers", "ps_staleness", "ps_commit_staleness",
    "ps_snapshots_total", "ps_snapshot_sets_total",
    # replication / HA
    "ps_replicas_attached_total", "ps_replicas_connected",
    "ps_replica_disconnects_total", "ps_replica_frames_total",
    "ps_replica_clock", "ps_replication_lag", "ps_promotions_total",
    # adaptive aggregation
    "ps_merged_commits_total", "ps_merge_queue_depth",
    "ps_rate_scaled_commits_total", "ps_backpressure_hints_total",
    # sharded client
    "ps_stripe_losses_total",
    # -- hub/client dotted series (histograms + span names) --------------------
    "ps.commit", "ps.pull", "ps.evict", "ps.merge", "ps.promote",
    "ps.reconnect", "ps.replica_attach", "ps.snapshot", "ps.snapshot_set",
    "ps.handle_commit", "ps.handle_pull",
    "ps.commit_bytes", "ps.commit_latency_ms", "ps.pull_latency_ms",
    "ps.pull_stall_ms", "ps.inflight_depth", "ps.serialize_ms",
    "ps.snapshot_ms", "ps.snapshot_set_ms", "ps.snapshot_fence_ms",
    "ps.reconnect_ms", "ps.reconnects",
    "ps.failover", "ps.failovers", "ps.failover_ms",
    "ps.replicate_ms", "ps.merge_batch",
    "ps.retry_after_ms", "ps.retry_after_wait_ms",
    "ps.backpressure_waits", "ps.stripe_lost",
    "ps.sparse_rows_pulled", "ps.sparse_rows_committed",
    "ps.sparse_wire_bytes_saved",
    # hyperscale embedding tier (ISSUE 15): hub hot-set estimate, client
    # hot-tier cache standing, sparse replication savings
    "ps.sparse_hot_rows",
    "ps_sparse_cache_hits_total", "ps_sparse_cache_misses_total",
    "ps.repl_sparse_bytes_saved",
    # self-scaling fleet (ISSUE 19): controller decisions
    "ps_fleet_spawns_total", "ps_fleet_retires_total",
    "ps_fleet_preemptions_total", "ps_fleet_target_size",
    # -- worker / health planes ------------------------------------------------
    "worker.restarts", "worker.preemptions",
    "health.event",
    # -- transport -------------------------------------------------------------
    "net_tx_frames_total", "net_tx_bytes_total",
    # zero-copy shm transport + batched receive (ISSUE 18): writes to the
    # shared-memory rings (a packed frame is one, a streamed commit one a
    # piece), producer parks on a full ring, and the
    # frames-per-syscall-batch histogram of the hub's batched receive
    "ps.shm_frames_total", "ps.shm_ring_full_waits", "ps_recv_batch_depth",
    # -- trainer / engine / data planes ----------------------------------------
    "trainer_epochs_total", "trainer_window_loss", "trainer.epoch",
    "engine_epoch_seconds", "engine.run_epoch",
    "async_windows_total", "async_window_wall_seconds",
    "async_window_device_seconds",
    "async.window",
    # -- leaf phases (obs.phase: ring + jax.profiler TraceAnnotation) ----------
    # worker loop, one set a window; seed and drain once a call
    "async.pull_wait", "async.h2d", "async.dispatch", "async.pull_land",
    "async.device_wait", "async.commit_d2h", "async.drain", "async.seed",
    # PS client inside ps.commit; hub handler thread and center lock
    "ps.commit_drain", "ps.commit_pack", "ps.commit_send",
    "ps.recv_commit", "ps.send_weights", "ps.apply",
    # sync plane and the trainer's feed
    "engine.place", "engine.dispatch", "engine.device_wait", "feed.wait",
    "feed_chunk_load_seconds", "feed_queue_depth", "feed_chunks_total",  # producer side
    "data.load",
    # the routed expert layer, from the counts the window program hands
    # back (models/transformer.py::routed_step_hook.publish)
    "moe_assignments_total", "moe_assignments_held_total",
    "moe_expert_load_max_over_mean",
    "moe_layer_calls_total", "moe_layer_calls_full_total",
    # device-side jax.named_scope names (models/transformer.py,
    # parallel/moe.py): op_name metadata of the compiled program, mapped
    # back to a trace's device events by obs.device_scopes
    "attn.sliding", "attn.full", "moe.route", "moe.dispatch", "moe.experts",
    "moe.shared", "moe.combine", "moe.bias",
    # the gated-delta-rule mixer (TransformerBlock._linear_attention) and
    # its four parts
    "attn.linear", "attn.linear.proj", "attn.linear.conv", "attn.linear.scan",
    "attn.linear.out",
    # the latent-attention mixer (TransformerBlock._latent_attention) and
    # its six parts
    "attn.latent", "attn.latent.q", "attn.latent.down", "attn.latent.up",
    "attn.latent.rope", "attn.latent.core", "attn.latent.out",
    # the parts of the step no mixer owns, for the device-time account
    # (obs.device_account): the dense MLP (TransformerBlock._dense_ffn), the
    # embedding and the head (TransformerLM), and the step's own loss, update
    # and window commit (parallel/engine.py)
    "ffn.dense", "lm.embed", "lm.head", "step.loss", "step.update", "step.commit",
    "punchcard.job",
})
