"""The embedded engine row-sharded over one host's chips: ``embed.py``'s
cell with the one line that differs — the engine is built as
``Sentinel(load_config(...), mesh=local_mesh(chips))``. Tap, generator,
``check()``/``control()`` and the plain reference are the one-chip
cell's: a sharded engine's semantics are the sequential ones."""

from __future__ import annotations

import functools
from unittest import mock

from chipbench.deployments.embed import EmbeddedEngineCell


class EmbeddedEngineMeshCell(EmbeddedEngineCell):
    def set_up(self) -> None:
        import sentinel_tpu as stpu
        from sentinel_tpu.parallel.local_shard import local_mesh
        # ``embed.py`` may not be edited and builds its engine inline, so
        # the constructor it looks up is the public one with the mesh bound
        meshed = functools.partial(
            stpu.Sentinel, mesh=local_mesh(self.cfg["chips"]))
        with mock.patch.object(stpu, "Sentinel", meshed):
            super().set_up()


BUILDERS = {"embedded_engine_mesh": EmbeddedEngineMeshCell}
