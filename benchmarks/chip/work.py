"""The least work each measured program has to do, counted from the
shapes of what it was asked, and the roofline share that work gives
against a measured device time.

A share counts only the work the answer needs: rows of the selected
clusters that hold a document (not the padded block), the postings the
query terms actually have (not the padded posting rows), codes of those
rows and the lookup tables. So it is a lower bound on the time, and a
share above 100% means the device time missed part of the program.
"""


def device_pipeline_work(useful_rows, postings, dim, itemsize=4):
    """One-jit device path (sparse retrieval, selection, dense scoring,
    fusion) for a batch: `useful_rows` document rows read from HBM and
    scored (2 flops per element), `postings` (doc id, weight) pairs of
    the query terms read. -> (flops, bytes)."""
    flops = 2 * useful_rows * dim + 2 * postings
    nbytes = useful_rows * dim * itemsize + postings * 8
    return flops, nbytes


def adc_work(useful_rows, nsub, batch, n_codes=256):
    """ADC scoring for a batch: one uint8 code per subspace read for each
    useful row, the (batch, nsub, n_codes) float32 lookup tables read
    once, and one lookup-add per code. -> (ops, bytes)."""
    ops = useful_rows * nsub
    nbytes = useful_rows * nsub + batch * nsub * n_codes * 4
    return ops, nbytes


def roofline_share(flops, nbytes, seconds, peaks):
    """(share in %, bound): the least time the chip could take for the
    work (the larger of flops over peak FLOP/s and bytes over peak HBM
    bandwidth) over the measured device time."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "memory" if t_bytes >= t_flops else "compute"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
