"""Served requests completed over the window (host clock): every request
submitted before the deadline, over the time until the last of them was
answered.  Failed or refused requests count as attempted and not
completed.  It measures throughput where the clients keep the service
saturated (a closed loop); under an open loop below capacity it would
only repeat the offered rate."""


def read(run):
    if not run.driver.startswith("service") or run.window.seconds <= 0:
        return None
    return run.window.completed / run.window.seconds
