"""Device seconds of the UNet's residual blocks (GroupNorm, SiLU, both
3x3 convolutions, the time projection, the skip, and in the up path the
skip's concatenation) in the denoise program, over the guided DDIM steps
served: sum over groups of n_cloud x batch, the denominator of
``denoise_s_per_image_step.poisson``.  Self-time of the device
operations whose innermost named scope is ``resblock``, inside the
harness's ``bench.process_group`` spans (device trace,
``bench/program_trace.py``)."""
from bench import program_trace


def read(run):
    return program_trace.scope_s_per_image_step(run, "resblock")
