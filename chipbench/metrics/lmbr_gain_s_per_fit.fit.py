"""Seconds per fit in LMBR's gain evaluation: the outermost ``lmbr.gain``
spans (``_LMBRState.max_gain_many``: the batched refresh and the one-pair
re-verify) over the fits completed in the window."""

from chipbench.harness import load_metric


def read(run):
    return load_metric("hpa_s_per_fit.fit").span_s_per_fit(run, "lmbr.gain")
