"""Kernel: the paged-attention kernel's share of its roofline (%): the
least time its calls in the traced part of the window could take,
max(FLOPs / peak, bytes / HBM peak) per call from the live context of
every row (``work.py``), over the kernel's device time in the trace.

Match rule: the served path holds one Pallas kernel, the paged
attention, and the trace names a Pallas call as an HLO custom call with
the target ``tpu_custom_call``."""

from work import kv_bytes_of, least_time

MATCH = 'custom_call_target="tpu_custom_call"'


def is_kernel(op) -> bool:
    return MATCH in op.name


def read(run):
    if run.replay is None or run.device_trace is None:
        return None
    kernel_s = run.device_trace.time_of(is_kernel)
    lt = least_time(run.model, run.replay.steps(*run.traced),
                    kv_bytes_of(run.cell.config["kv_dtype"]),
                    run.peaks["bf16_flops"], run.peaks["hbm_bytes_per_s"])
    if not kernel_s or lt is None:
        return None
    print(f"paged_attn_roofline: least {lt['least_s']:.6f} s "
          f"(memory-bound {lt['memory_bound_s']:.6f} s, compute-bound "
          f"{lt['compute_bound_s']:.6f} s) over kernel time "
          f"{kernel_s:.6f} s", flush=True)
    return 100.0 * lt["least_s"] / kernel_s
