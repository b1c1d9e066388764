"""Share of the HBM roofline that one Hessian-vector product of the
timed solve reaches: the Newton driver's own HVP (Algorithm 1 through
the SELL-C-σ layout in this cell), its least traffic over its device
seconds in the traced run's probe (``psc_solve_lfr.hvp_probe``, device
trace), over the device's HBM bandwidth (``bench/peaks.json``), in %.
Moves solve_s."""


def min_bytes(n: int, nnz: int, k: int) -> int:
    """The least one product Hess F_p(U) @ eta can move whatever the
    layout or algorithm: each stored entry's float32 value and int32
    column once, and three (n, k) float32 blocks (U and eta in, the
    product out).  The driver builds Algorithm 1's W-hat (nnz x k
    values) inside each product, so that is work above the least, not
    part of it."""
    return nnz * (4 + 4) + 3 * n * k * 4


def read(run):
    probe, peaks = run.get("hvp_probe"), run.get("peaks")
    if not probe or not probe.get("hvp_s") or probe["hvp_s"] <= 0 \
            or not peaks:
        return None
    moved = min_bytes(probe["n"], probe["nnz"], probe["k"])
    return 100.0 * moved / probe["hvp_s"] / peaks["hbm_bytes_per_s"]
