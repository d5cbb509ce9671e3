"""c4_statescan: the SSD inter-chunk recurrence
``run[c] = a[c] · run[c-1] + s[c]`` over chunk states ``s`` of shape
(batch, chunks, heads, headdim, state), f32, with one decay per
(batch, chunk, head). Least work: one multiply and one add per state
element; the states read once, the running states written once, the
decays read once."""

TRACE = r"chunk_scan_pallas"


def work(batch: int, chunks: int, heads: int, headdim: int, state: int,
         itemsize: int = 4) -> tuple[float, float]:
    elems = batch * chunks * heads * headdim * state
    decays = batch * chunks * heads
    return float(2 * elems), float((2 * elems + decays) * itemsize)
