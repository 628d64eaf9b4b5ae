"""The cell benchmark of theanompi_tpu: harness, yardstick and data.

Entry point: ``python3 -m benchmark.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.  Everything
that decides a number lives in this directory; from the program it
takes the system under test, its spans, counters and kernel names.
"""
