"""How long the TPU runtime takes to start, read from the program's own
gauge ``device_runtime_start_seconds``: one fresh process whose first
touch of JAX's backends is ``TPUPlace().jax_device()``. (In a benchmark
run the harness's ``find_chips`` touches them first, so the gauge stays
unset there: PERF.md section 7.)

    python3 benchmark/tools/runtime_start.py        # through the chip tool
"""
import json
import os
import sys
import time

T0 = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    import jax                      # noqa: F401  (its import is timed apart)

    t_jax = time.perf_counter()
    import paddle_tpu as fluid
    from paddle_tpu import monitor

    t_program = time.perf_counter()
    place = fluid.CPUPlace() if "--rehearse" in sys.argv else fluid.TPUPlace()
    dev = place.jax_device()
    print(json.dumps({
        "import_jax_s": t_jax - T0, "import_paddle_tpu_s": t_program - t_jax,
        "device_runtime_start_s": monitor.metric_value(
            "device_runtime_start_seconds", None),
        "first_jax_device_call_s": time.perf_counter() - t_program,
        "device": {"platform": dev.platform, "kind": dev.device_kind}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
