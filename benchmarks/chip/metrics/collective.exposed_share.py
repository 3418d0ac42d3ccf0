"""collective.exposed_share (%): the share of the traced window in which a
collective (the halo ``ppermute``s, the ``psum``s) ran on a chip with no
other operation beside it, averaged over the cell's chips."""


def read(ctx):
    tr = ctx.trace
    if tr is None or len(tr.devices) < 2 or tr.window_s <= 0:
        return None
    return 100.0 * tr.exposed_collective_s() / tr.window_s
