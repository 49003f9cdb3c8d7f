"""Share of the traced window in which no operation runs on the device,
averaged over the chips used."""


def read(run):
    tr = run.trace
    shares = [1.0 - tr.busy_s(dev) / tr.window_s for dev in tr.devices]
    return 100.0 * sum(shares) / len(shares) if shares else None
