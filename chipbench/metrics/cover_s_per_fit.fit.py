"""Seconds per fit in the span engine: the outermost ``cover.batch``
spans (``batched_cover_csr``), in LMBR and in the plan's spans, over the
fits completed in the window."""

from chipbench.harness import load_metric


def read(run):
    return load_metric("hpa_s_per_fit.fit").span_s_per_fit(run,
                                                           "cover.batch")
