"""Encode + H2D of the shapes' columns, from ``phase.staging``."""


def read(run):
    return run.after_setup.get("server.timer.phase.staging.ms", 0.0) / 1000.0
