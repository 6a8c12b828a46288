"""Peak device memory of the run (`memory_stats()["peak_bytes_in_use"]`,
read after the window and before the reference runs) over the chip's HBM
from `bench/peaks.json`.  Memory bounds how many trees a level program
can batch."""


def read(run):
    peak = run["device"].get("memory_peak_bytes", 0)
    if run["kind"] != "train" or peak <= 0:
        return None
    return 100.0 * peak / run["peaks"]["hbm_bytes"]
