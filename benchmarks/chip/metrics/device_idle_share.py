"""Share of the traced window, in %, in which no op ran on the device.

One reader for every cell: ``device_idle_share.<what it moves>`` finds
it by its first part. A device that ran nothing reads 100; a run with
no trace to read reads nothing."""


def read(run):
    if not run.device or not run.device["window_s"]:
        return None
    return 100 * (1 - run.device["busy_s"] / run.device["window_s"])
