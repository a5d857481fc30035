"""stage_device_share: the share of the window's staged chunks gathered
from a sample store on the device, in percent. The program books the
counter "stage_device" (zero seconds) for each chunk it gathers on the
device, and places the store once for a run's chunks, so every chunk of
the window takes the same path: this reads 100 where the window's
phases hold "stage_device" beside "stage_gather" seconds, and ``None``
where the program books no "stage_device" (a host-gather run, or a
program without the device store)."""


def read(r):
    phases = r.win["phases"]
    return 100.0 if "stage_device" in phases and phases.get(
        "stage_gather") else None
