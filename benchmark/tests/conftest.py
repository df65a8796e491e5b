"""The benchmark's own tests run on the CPU, at sizes a test run can hold:
JAX is held to the CPU before anything imports it, and the program's Pallas
kernels run interpreted there."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
