"""Device time in collective operations while no other operation ran on that
device, over the traced window, mean over the devices."""


def read(ctx):
    if not ctx.traced or ctx.traced["devices"] < 2:
        return None
    return 100.0 * ctx.traced["collective_exposed_s"] / ctx.traced["window_s"]
