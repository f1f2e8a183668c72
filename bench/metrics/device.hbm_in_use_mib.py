"""Device memory in use (MiB) on the fullest chip once the window has
closed: the state the window leaves, beside ``hbm_peak_mib``, which also
holds the peaks of set-up."""


def read(run):
    if not run.hbm_in_use_bytes:
        return None
    return run.hbm_in_use_bytes / (1 << 20)
