"""Bytes a launch's bind copies, in MB: the median over the traced
window's launches of the ``copied_bytes`` counter of
``hetgpu.launch.bind``.  The bind copies only the buffers the launch
writes; those it only reads are bound by reference.  A program whose
bind carries no such counter reads as ``None``."""
import program_spans as ps


def read(ctx):
    r = ps.read(ctx)
    if r is None:
        return None
    return ps.median([float(ev[3]["copied_bytes"]) / 1e6
                      for ev in ps.named(r, "launch.bind")
                      if "copied_bytes" in ev[3]])
