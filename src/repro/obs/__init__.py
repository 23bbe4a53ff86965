"""repro.obs — trace-safe telemetry, profiling spans and scopes, and
structured event logs (DESIGN.md §11).

Three pieces, one vocabulary:

  * ``obs.telemetry`` — the metric catalogue and the pure-pytree in-trace
    carry the sweep/mesh engines thread through their scans (statically
    gated: disabled == byte-identical trace);
  * ``obs.events``    — the host-side JSONL event sink + schema +
    ``summarize`` (fed at ``eval_every`` points, never from device code);
  * ``obs.spans``     — ``span()`` wall-clock + ``jax.profiler`` wrappers
    and Perfetto capture (``profile`` / ``perfetto_artifacts``).

Host spans of ``launch/train.py``'s loop: ``train/batch``,
``train/dispatch``, ``train/loss_fetch``, ``train/compile+first_step`` and
``train/steady_steps``, written once per name (count, total) at the end of
the run.  Device scopes (``jax.named_scope``, metadata only, in the compiled
HLO's ``op_name``): the train step's phases ``model``, ``wire`` (with
``wire/bucketize``, ``wire/encode``, ``wire/ring``, ``wire/memory``,
``wire/downlink``, ``wire/unbucketize``) and ``optimizer``
(``core/dist.py``), and inside the ring ``ring_hop`` and ``bucket_acc``
(``kernels/bucket_ring.py``).

CLI: ``python -m repro.obs {summary,validate,diff,dashboard,smoke} ...``
"""
from repro.obs import events, spans, telemetry                   # noqa: F401
from repro.obs.events import EventLog, read_events, summarize    # noqa: F401
from repro.obs.spans import profile, span                        # noqa: F401
