"""Thread-seconds a save spends in host memory alone: the assembly
buffers' ``np.empty`` and the copies into them (the program's
``stage.alloc`` and ``stage.copy`` spans), wherever its staging runs."""

from perfbench.phase_spans import stage_thread_seconds_per_save


def read(obs):
    return stage_thread_seconds_per_save(obs, "alloc", "copy")
