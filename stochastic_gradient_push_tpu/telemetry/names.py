"""The one vocabulary of trace names: what the compiled step, the Pallas
kernels and the host loops call themselves inside a ``jax.profiler``
capture.  Defined once so that the three places that use a name — the
program that writes it, the benchmark reader that sums it
(``benchmark/readers/program_trace.py``) and PERF.md's table — cannot
drift apart.  Nothing here imports anything: ``ops/`` and ``utils/`` read
it without pulling the telemetry package's own dependencies.

One clock: every name below lands in the profiler's XPlane — scopes and
kernel names on the device planes (as the operations' ``op_name``), host
spans and the step marker on the host plane — so an idle gap of the
device can be put down to the host span it fell in.  ``SpanTracer``'s
``trace.json`` (tracer.py) is the operator's whole-run view on the host's
wall clock; the loops' spans reach it by calls of their own.  Set-up is the
exception: ``telemetry.setup_phase`` (setup_ledger.py) takes two
``time.time()`` readings and hands the same two to the set-up ledger and to
``trace.json`` (phase ``setup``), and opens ``sgp:setup.<name>`` in
whatever capture is running — one call, three sinks.  ``time.time()`` is
also the clock of JAX's own build events (``JAX_*_EVENT`` below), so the
ledger holds both sides on one clock with no conversion.
"""

# -- device side: jax.named_scope around the phases of the train step ----
SCOPE_PRE_STEP = "sgp.pre_step"          # overlap launch, de-bias
SCOPE_FORWARD = "sgp.forward"            # model apply + loss; autodiff
#                                          marks its transpose (backward)
SCOPE_REDUCE_GRADS = "sgp.reduce_grads"  # exact gradient averaging
SCOPE_OPTIMIZER = "sgp.optimizer"        # LR schedule, tx.update, update
SCOPE_GOSSIP = "sgp.gossip"              # post_step: the push-sum round
SCOPE_WIRE = "sgp.gossip.wire"           # nested: the wire codec
SCOPE_HEALTH = "sgp.health"              # grad norm + health signals
STEP_SCOPES = (SCOPE_PRE_STEP, SCOPE_FORWARD, SCOPE_REDUCE_GRADS,
               SCOPE_OPTIMIZER, SCOPE_GOSSIP, SCOPE_HEALTH)

# -- inside sgp.forward: the LM's state-space mixer (models/ssm.py) ------
SCOPE_SSM_MIXER = "lm.ssm_mixer"         # the whole Mamba-2 mixer
SCOPE_CONV1D = "lm.conv1d"               # nested: causal depthwise conv
SCOPE_SSD = "lm.ssd"                     # nested: the chunked scan alone
# -- the gated short-convolution mixer (models/shortconv.py; its
# convolution is under SCOPE_CONV1D too) and the top-k expert layer
# (models/moe.py::topk_moe_ffn) ------------------------------------------
SCOPE_CONV_MIXER = "lm.conv_mixer"       # the whole mixer, projections in
SCOPE_MOE = "lm.moe"                     # router to combine
SCOPE_MOE_ROUTE = "lm.moe.route"         # nested: scores, top-k, sort,
#                                          the rows' gather and scatter
SCOPE_MOE_EXPERTS = "lm.moe.experts"     # nested: the grouped products
SCOPE_MOE_SHARED = "lm.moe.shared"       # nested: the shared expert
# -- the gated delta-rule mixer (models/gated_deltanet.py; its convolution
# is under SCOPE_CONV1D too) -----------------------------------------------
SCOPE_DELTA_MIXER = "lm.delta_mixer"     # the whole mixer, projections in
SCOPE_DELTA_RULE = "lm.delta_rule"       # nested: the chunked rule alone
# -- latent attention (models/transformer.py::_LatentAttention) and the
# multi-token-prediction module (its norms, eh_proj and block; its head
# pass is under SCOPE_LM_HEAD) -------------------------------------------
SCOPE_MLA = "lm.mla"                     # the whole mixer, projections in
SCOPE_MTP = "lm.mtp"
# -- the head (models/transformer.py) and the loss (train/lm.py::lm_loss):
# every operation over an array of the logits' size ----------------------
SCOPE_LM_HEAD = "lm.head"                # the head's product, the loss

# -- the jitted steps' names: the compiled module is ``jit_<name>`` on the
# trace's "XLA Modules" line, which tells the step from set-up's programs
MODULE_TRAIN_STEP = "sgp_train_step"
MODULE_TRAIN_STEP_SCAN = "sgp_train_step_scan"
MODULE_LM_TRAIN_STEP = "sgp_lm_train_step"
MODULE_LM_TRAIN_STEP_SCAN = "sgp_lm_train_step_scan"

# -- pallas_call names ---------------------------------------------------
KERNEL_FLASH_FWD = "flash_fwd"
KERNEL_FLASH_BWD = "flash_bwd"            # the fused backward
KERNEL_FLASH_DQ = "flash_dq"              # the pair it gives way to
KERNEL_FLASH_DKV = "flash_dkv"            # beyond its VMEM budget
KERNEL_SSD_FWD = "ssd_fwd"                # the scan inside a chunk
KERNEL_SSD_BWD = "ssd_bwd"                # (ops/ssd.py), and its backward
KERNEL_DELTA_FWD = "delta_fwd"            # the chunked delta rule
KERNEL_DELTA_BWD = "delta_bwd"            # (ops/delta_rule.py), and back
KERNEL_GROUPED_MATMUL = "grouped_matmul"        # the expert layer's
KERNEL_GROUPED_MATMUL_DW = "grouped_matmul_dw"  # products (ops/grouped_matmul.py)
KERNEL_GOSSIP_START = "gossip_edge_start"
KERNEL_GOSSIP_WAIT = "gossip_edge_wait"
KERNEL_PAGED_ATTENTION = "paged_attention"

# -- host side: ProfileWindow.span / .step (utils/profiling.py) ----------
HOST_SPAN_PREFIX = "sgp:"
HOST_STEP = "sgp_step"
# the loops' spans, under the names SpanTracer already gives them
HOST_SPANS = ("data_fetch", "dispatch", "fence", "metrics_fetch", "health",
              "async_bilat", "checkpoint_save", "validate",
              "recovery_global_average")
# set-up's phases (telemetry.setup_phase): ``sgp:setup.<name>`` in a
# capture, ``<name>`` on trace.json's ``setup`` track and in the ledger
SETUP_SPAN_PREFIX = HOST_SPAN_PREFIX + "setup."
SETUP_SPANS = ("parse", "mesh", "plan", "model", "state_init", "data",
               "resume", "first_step")
# the jitted steps by the name JAX's build events give them; the first one
# built ends set-up (the ledger's cut)
STEP_MODULES = (MODULE_TRAIN_STEP, MODULE_TRAIN_STEP_SCAN,
                MODULE_LM_TRAIN_STEP, MODULE_LM_TRAIN_STEP_SCAN)

# -- jax.monitoring events the set-up ledger listens to (jax 0.9): the
# three time spans carry ``fun_name`` and [start, end] on time.time() ----
JAX_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
JAX_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
JAX_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
JAX_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
JAX_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"  # on a write
JAX_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
JAX_CACHE_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"

