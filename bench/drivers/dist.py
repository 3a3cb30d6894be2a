"""Driver ``dist``: the planned slab/pencil transform over a mesh of chips.

The ``batch`` driver's set-up, window and check, with the config's client
(``DistFFTND``) planned under ``use_mesh(flat_mesh(devices))``: the planner
chooses the decomposition, nothing pins it.  Set-up also prints the
all-to-alls in each compiled direction.  The check gathers the sharded
result.
"""

from __future__ import annotations

from drivers import batch


class Cell(batch.Cell):
    def __init__(self, config, traffic, seed, devices, log):
        from repro.launch.mesh import flat_mesh, use_mesh
        from repro.roofline.hlo_parse import count_source_collectives

        self._mesh_cm = use_mesh(flat_mesh(devices[:config["chips"]]))
        self._mesh_cm.__enter__()
        try:
            super().__init__(config, traffic, seed, devices, log)
        except BaseException:
            self._mesh_cm.__exit__(None, None, None)
            raise
        for c in self.clients:
            log(f"collectives {c.problem.signature()} "
                f"plan={c.plan.candidate.key()} all_to_all_fwd="
                f"{count_source_collectives(c._fwd_compiled.as_text())} "
                f"all_to_all_inv="
                f"{count_source_collectives(c._inv_compiled.as_text())}")

    def close(self) -> None:
        super().close()
        self._mesh_cm.__exit__(None, None, None)
