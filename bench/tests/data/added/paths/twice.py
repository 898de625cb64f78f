"""A traffic path given as a file only: a closed loop that launches each
event twice on the same buffers and keeps the second launch's outputs."""
import loadgen


class TwicePath(loadgen.LaunchPath):
    def _one(self, i, u):
        super()._one(i, u)
        return super()._one(i, u)


PATH = TwicePath
