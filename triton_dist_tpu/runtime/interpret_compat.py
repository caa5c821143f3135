"""Workaround for a busy-spin in Pallas TPU interpret mode.

``jax._src.pallas.mosaic.interpret.shared_memory.Semaphore.wait`` busy-spins
(``while True: ... continue``) when waiting on a DMA semaphore whose
matching DMA has not been issued yet. On low-core-count hosts (CI boxes),
the spinning waiter threads starve the device threads
that would issue those DMAs — GIL + lock-convoy on the shared-memory lock —
so multi-device kernels hang nondeterministically.

This module monkeypatches (in-process only) the spin loop to sleep briefly
between polls, yielding the GIL so sender devices make progress. Applied
lazily the first time an interpreted kernel is requested
(ops.common.resolve_interpret).
"""

from __future__ import annotations

import time

_PATCHED = False

_SPIN_SLEEP_S = 2e-4


def patch_interpreter_spin() -> None:
    """Idempotently patch Semaphore.wait to yield while polling."""
    global _PATCHED
    if _PATCHED:
        return
    # Private modules of the installed jax (0.9.0): an import error here
    # means the interpreter moved and this patch needs re-deriving —
    # fail loudly rather than hang later on the unpatched spin.
    from jax._src.pallas.mosaic.interpret import shared_memory
    from jax._src.pallas.mosaic.interpret import vector_clock as vc

    def wait(self, value, global_core_id, *, has_tasks=False):
        global_core_id = int(global_core_id)
        clock = None
        if not has_tasks:
            with self.cv:
                while self.count_by_core[global_core_id] < value:
                    self.cv.wait()
                self.count_by_core[global_core_id] -= value
                if self.detect_races:
                    clock = vc.copy_vector_clock(
                        self.clocks[global_core_id])
            if self.detect_races:
                with self.shared_memory.lock:
                    vc.update_vector_clock(
                        self.shared_memory.clocks[global_core_id], clock)
            return

        while True:
            clock = None
            with self.cv:
                if self.count_by_core[global_core_id] >= value:
                    self.count_by_core[global_core_id] -= value
                    if self.detect_races:
                        clock = vc.copy_vector_clock(
                            self.clocks[global_core_id])
                    else:
                        return
            if clock is not None:
                with self.shared_memory.lock:
                    vc.update_vector_clock(
                        self.shared_memory.clocks[global_core_id], clock)
                return

            with self.shared_memory.lock:
                task_queue = self.shared_memory.tasks_by_sem[
                    (self.id, global_core_id)]
                task = task_queue.pop() if len(task_queue) > 0 else None
            if task is None:
                # Upstream `continue`s here without yielding, starving the
                # device thread that would issue the DMA we are waiting for.
                time.sleep(_SPIN_SLEEP_S)
                continue
            task()

    shared_memory.Semaphore.wait = wait
    _PATCHED = True
