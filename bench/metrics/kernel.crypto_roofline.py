"""Roofline share of the cipher and digest kernels together
(``kernels/crypto.py``: ``arx_cipher`` reads and writes the payload words,
``keyed_hash`` reads them; see ``bench/workcount.py``) at the chip's HBM
peak, over the two kernels' summed device time in the trace (device
trace)."""
from bench import workcount

CIPHER = "arx_cipher"
DIGEST = "keyed_hash"


def read(run):
    red = run.reduced
    if red is None or run.peaks is None:
        return None
    c_secs, c_calls = red.op_time(CIPHER)
    d_secs, d_calls = red.op_time(DIGEST)
    if not c_calls or not d_calls or c_secs + d_secs <= 0:
        return None
    mix = run.cell.mix
    need = (c_calls * workcount.cipher_bytes(mix["batch"], mix["pkt_bytes"])
            + d_calls * workcount.digest_bytes(mix["batch"], mix["pkt_bytes"]))
    return 100.0 * workcount.least_seconds(need, run.peaks) / (c_secs + d_secs)
