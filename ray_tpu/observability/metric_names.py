"""Declared metric-name registry — the source of truth lint rule R22
checks call sites against.

A typo'd histogram name (``perf.observe("task.exeute", ...)``) does not
fail; it silently creates a parallel family that every consumer (head
quantiles, ``ray-tpu top``, doctor baselines) ignores.  Same for a
misspelled goodput ledger category, which would break the ledger's
exclusivity-sums-to-wall-clock invariant.  So: every literal name passed
to ``perf.observe(...)`` and every ledger category passed to
``goodput.account(...)`` / ``goodput.interval(...)`` must appear here
(or be imported from this module); raylint R22 flags the rest.

This module is deliberately import-free (no config, no runtime) so the
linter and the hot paths can both load it for nothing.
"""

from __future__ import annotations

# Goodput ledger categories, in display order.  Exclusive: every wall-
# clock second of a job lands in exactly one.  ``idle`` is derived
# (wall minus everything attributed), never accounted directly.
LEDGER_CATEGORIES = (
    "compute",
    "compile",
    "data_wait",
    "collective_wait",
    "ckpt_stall",
    "restart_downtime",
    "idle",
)

# Every perf-plane histogram family the runtime records.  Grouped by
# subsystem prefix (the ``--subsystem`` filter in ``ray-tpu top``).
PERF_HISTOGRAMS = frozenset({
    # rpc
    "rpc.call",
    "rpc.connect",
    # task plane
    "task.execute",
    "task.e2e",
    "task.sched",
    # object plane
    "fetch.object",
    "fetch.stripe",
    "push.object",
    # striped transport
    "transport.striped_run",
    "transport.chunk",
    # checkpoint engine
    "ckpt.save",
    "ckpt.hash",
    "ckpt.write",
    "ckpt.commit",
    # serve
    "serve.request",
    "serve.queue_wait",
    "serve.execute",
    "serve.serialize",
    "serve.ingress_put",
    "serve.replica_exec",
    # train loop
    "train.step",
    "train.report",
    "train.ckpt_enqueue",
    # jit compile detection (goodput ledger's runtime mirror of R21)
    "jit.compile",
    # drain / lifecycle
    "drain.migrate",
    # comms plane (collective rendezvous phases; observability/comms.py)
    "collective.op",       # full API-layer op duration (collective.py seam)
    "collective.launch",   # last-arrival compute / compiled-program run
    "collective.collect",  # per-rank blocked time from arrival to result
    "collective.quantize",  # per-rank block-quantization cost (compression
                            # tier, collective/quantization.py)
})

# Every span the program opens by a literal name (``observability.span`` /
# ``task_span``).  In a profiler session each appears in the ``.xplane.pb``
# as ``ray_tpu.<name>``; these names are the contract with whatever reads
# the trace (``benchmark/program_spans.py``), so a rename shows up here.
# ``rpc:<METHOD>`` spans and user ``profile_span``s are named at run time
# and are not listed.
SPANS = frozenset({
    # serve: the proxy's handler thread, from arrival to the reply written
    "serve.request",
    "serve.route",          # handle lookup + Router._pick + the submit
    "serve.await_replica",  # handle.remote(...).result() after the submit
    "serve.reply",          # the reply encoded and written
    # serve: the replica batcher's flusher thread
    # wake-up with a non-empty queue -> batch cut; depth, cap,
    # oldest_wait_us and left (queued at the cut and not taken by it);
    # cut (the reason that fired: full, waited, passed, not_due) and the
    # two estimates the last one compares, gap_est_us (EWMA of the gaps
    # between admissions) and call_est_us (the per-item EWMA), -1 where
    # the replica has none yet
    "serve.batch.linger",
    # _run_batch: pad, call, deliver; n, padded_n (rows after
    # pad_batch_to), size_sum and size_max (the members' observed sizes):
    # the padded rectangle's fill is size_sum / (padded_n x size_max)
    "serve.batch.execute",
    "serve.batch.call",     # the user's callable alone
    # runtime
    "task.execute",         # one task on a worker thread
    "actor.call",           # one method call on an actor's thread
    "actor.init",           # the actor's constructor, with the device grant
    # object plane
    "object.fetch",
    # checkpoint engine
    "checkpoint.save",
    "checkpoint.hash",
    "checkpoint.write",
    "checkpoint.gather",
    "checkpoint.commit",
    # parallel/expert.py: one a forward, from the host side of the expert
    # layer's call-back; held, absent, zero (routed pairs by where the
    # expert lives), load_max (the most-loaded held expert's pairs, summed
    # over the layers), layers, experts: the counters' increments
    "moe.route",
})

# Every scope the model enters on the device by a literal name
# (``jax.named_scope``), the device-side twin of ``SPANS``: a scope lands
# in the name stack of the operations traced inside it, the compiler
# keeps that as each operation's ``op_name``, and the TPU's profiler
# writes it to the ``.xplane.pb`` as the operation's ``tf_op`` (forward
# ``jit(step)/.../attn/core/pallas_call``, backward inside
# ``transpose(jvp(...))``).  Single tokens, none a host span's name; they
# are the contract with whatever reads a device trace
# (``benchmark/device_scopes.py``, TensorBoard's framework-operation
# view), so a rename shows up here.
DEVICE_SCOPES = frozenset({
    "embed",      # the embedding lookup and its scale
    # a layer's token mixer: norm, projections, rotary positions, the
    # attention itself, output projection and the residual's add
    "attn",
    "core",       # inside ``attn``: the attention alone (a flash kernel)
    "mlp",        # a layer's dense FFN with its norm and residual
    "moe",        # the routed mixture (``parallel/expert.py``)
    "router",     # inside ``moe``: logits, softmax, top-k
    "experts",    # inside ``moe``: gather, grouped products, scatter-add
    "head",       # final norm, lm head, cross entropy, exit gate
    "optimizer",  # ``train/step.py``: the update and the gradient's norm
})

# The gauge a replica sets once, when its constructor returns.
REPLICA_INIT_GAUGE = "serve_replica_init_seconds"

# ``Replica.get_metrics()``: the same two numbers summed over every batch a
# replica has run (real sizes; rows x largest size), so that the fill is
# there without a trace.
REPLICA_BATCH_SIZE_SUM = "batch_size_sum"
REPLICA_BATCH_PADDED_SUM = "batch_padded_sum"
# The cuts a replica's batcher has made, and those of them made because no
# neighbour was due (``cut`` == "not_due" on ``serve.batch.linger``) and not
# because the batch was full, the oldest request had waited the bound out
# or an earlier cut had passed it over.
REPLICA_BATCH_CUTS = "batch_cuts"
REPLICA_BATCH_CUTS_NOT_DUE = "batch_cuts_not_due"

# Comms-plane sample families.  Not literal-checked by a lint rule the
# way perf.observe names are — they are declared here so the exporters
# (observability/comms.py, collective/tensor_plane.py) and their
# consumers (dashboard head, doctor, tests) share one spelling.
COMMS_FAMILY = "raytpu_comms_bytes"
TPLANE_EPOCH_GAUGE = "tplane_epoch"
