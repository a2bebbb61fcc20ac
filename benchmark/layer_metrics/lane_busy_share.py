"""The lane's own device-busy share of the window: the closed occupancy
windows of ``lane.deviceBusy`` (launch call entered to output seen
ready, the union over outstanding launches) over the window's length.
Reads above 100 less ``device_idle_share`` by the launch call and the
wake-up of the waiter, which the lane cannot tell from the kernel."""


def read(run):
    if not run.delta("server.timer.lane.deviceBusy.n"):
        return None
    return 100.0 * run.delta("server.timer.lane.deviceBusy.ms") / 1000.0 / run.window_s
