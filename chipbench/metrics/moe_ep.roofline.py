"""The held-expert layer executables' share of their roofline:
``kernels_roofline``'s computation (each dispatch's least time at the
stated bits, summed, over the layer executables' device time in the
trace), which reads the cell's own ``dispatch_work``: here
``families/moe_ep.py``'s (the router over every expert, the held
experts' part of the routed FFNs, the shared FFN)."""

from pathlib import Path

from chipbench import harness

read = harness.load_module(Path(__file__).with_name("kernels_roofline.py"),
                           "chipbench_metric_kernels_roofline").read
