"""Peak bytes in use on the fullest chip over the chip's HBM, in percent."""


def read(run):
    if run.peaks is None or not run.memory_peak_bytes:
        return None
    return 100.0 * run.memory_peak_bytes / run.peaks["hbm_bytes"]
