"""Lowering subsystem: compile solved dataflow schemes into executable
plans, run them on one of three backends, verify them against the
``kernels/ref.py`` oracles, and calibrate the cost model against
measured runtimes.

  kind table
      kinds.KINDS                  one record per executable layer kind:
         input shape, lone-plan operands, weight initialiser, how the
         graph walk feeds it
  layer tier
      solver (LayerScheme)
          -> plan.lower_scheme                      (KernelPlan)
          -> exec.execute_plan / verify_plan / measure_plan
  network tier
      solver (NetworkSchedule, or schedule.lower(graph, hw))
          -> netplan.lower_network                  (NetworkPlan: ordered
             kernel plans + segment buffer schedule w/ on-chip forwarding)
          -> netexec.execute_network / verify_network / measure_network

Every executor runs one graph walk (``netexec.layer_output``) on a map
from kind to kernel, one map per tier: ``exec.pallas_kernels`` (the
``interpret`` oracle backend and ``pallas``), ``fuse.compiled_kernels``
(``compiled``: ``fuse.fused_runner`` traces whole segments or the whole
net into one executable, cached process-wide by ``fuse.plan_signature``
— the default measured backend) and ``exec.ORACLE_KERNELS`` (the
reference).  ``kernels.backend`` names the backends.

  calibration
      calibrate.run_calibration          (per-kernel Spearman + fit,
         per-backend coefficients)
      calibrate.run_network_calibration  (end-to-end network Spearman)

Adding a layer kind takes one ``kinds.KINDS`` entry, one kernel in each
tier's map, and the kind's cost terms (its ``workloads.layers``
constructor).
"""
from .plan import GridAxis, KernelPlan, lower_scheme, lower_schedule
from .exec import (execute_plan, make_inputs, measure_plan,
                   reference_output, verify_plan)
from .netplan import (NetworkPlan, SegmentPlan, TensorPlacement,
                      lower_cached, lower_network)
from .netexec import (compare_network, execute_network, make_network_inputs,
                      measure_network, network_runner, reference_network,
                      verify_network)
from .fuse import (FusedNetwork, cache_stats, clear_cache,
                   compiled_plan_fn, fused_runner, plan_signature)
from .calibrate import (fit_calibration, run_calibration,
                        run_network_calibration, save_record, spearman)

__all__ = [
    "GridAxis", "KernelPlan", "lower_scheme", "lower_schedule",
    "execute_plan", "make_inputs", "measure_plan", "reference_output",
    "verify_plan",
    "NetworkPlan", "SegmentPlan", "TensorPlacement", "lower_cached",
    "lower_network",
    "compare_network", "execute_network", "make_network_inputs",
    "measure_network", "network_runner", "reference_network",
    "verify_network",
    "FusedNetwork", "cache_stats", "clear_cache", "compiled_plan_fn",
    "fused_runner", "plan_signature",
    "fit_calibration", "run_calibration", "run_network_calibration",
    "save_record", "spearman",
]
