"""The deployment every cell runs (copied from ``chip_smoke.py``'s
``served_path``, PR 24): one process holding a coordination server,
``holders`` loopback peers on the native C backend, and one backing-up
``ClientApp`` on the device backend, allowances granted directly as
``scenario/harness.py`` does.  Every ``defaults`` value is the program's;
the configuration's file states the ones a user depends on, and a
program whose defaults have moved away from them is refused."""

from __future__ import annotations

import sys
from pathlib import Path

from benchmark import specs

GRANT_BYTES = 1 << 40  # per holder; never the limit in a run


class Deployment:
    def __init__(self, work: Path, config: dict, rehearse: bool):
        self.work = work
        self.config = config
        self.rehearse = rehearse
        self.server = None
        self.client = None
        self.holders = []
        self.summaries = []  # the engine's end-of-backup summary events

    def _check_defaults(self) -> None:
        from backuwup_tpu import defaults
        dep = self.config["deployment"]
        stated = {
            "rs_k": defaults.RS_K, "rs_m": defaults.RS_M,
            "packfile_target_bytes": defaults.PACKFILE_TARGET_SIZE,
            "buffer_limit_bytes": defaults.PACKFILE_LOCAL_BUFFER_LIMIT,
            "buffer_resume_bytes": defaults.PACKFILE_RESUME_THRESHOLD}
        for key, have in stated.items():
            if int(dep[key]) != int(have):
                raise specs.SpecError(
                    f"configuration {self.config['name']} states {key} = "
                    f"{dep[key]}, the program's default is {have}")
        if int(dep["holders"]) != defaults.RS_K + defaults.RS_M:
            raise specs.SpecError("holders != rs_k + rs_m")

    def _client_backend(self):
        """``None``: the program selects (``select_backend()``).  A
        rehearsal on the CPU has to name the device backend, which
        ``select_backend()`` picks only where a chip is attached."""
        from backuwup_tpu.ops.backend import TpuBackend
        from backuwup_tpu.ops.gear import CDCParams
        stated = CDCParams(**{k: int(v)
                              for k, v in self.config["cdc"].items()})
        kind = self.config["deployment"]["client_backend"]
        if kind == "program_default":
            if CDCParams() != stated:
                raise specs.SpecError(
                    f"configuration {self.config['name']} states {stated},"
                    f" the program's default is {CDCParams()}")
            if not self.rehearse:
                return None
        elif kind != "tpu":
            raise specs.SpecError(f"unknown client_backend {kind!r}")
        return TpuBackend(stated)

    async def start(self) -> dict:
        from backuwup_tpu.app import ClientApp
        from backuwup_tpu.net.server import CoordinationServer
        from backuwup_tpu.ops.backend import NativeBackend

        self._check_defaults()
        self.work.mkdir(parents=True, exist_ok=True)
        self.server = CoordinationServer(
            db_path=str(self.work / "server.db"))
        port = await self.server.start()
        addr = f"127.0.0.1:{port}"

        def app(name: str, **kw) -> ClientApp:
            return ClientApp(config_dir=self.work / name / "cfg",
                             data_dir=self.work / name / "data",
                             server_addr=addr, tls=False, **kw)

        backend = self._client_backend()
        self.client = app("client",
                          **({} if backend is None else {"backend": backend}))
        self.holders = [app(f"h{i}", backend=NativeBackend())
                        for i in range(int(
                            self.config["deployment"]["holders"]))]
        self.client.messenger.subscribe(self._on_event)
        for a in [self.client] + self.holders:
            await a.start()
        for h in self.holders:
            self.client.store.add_peer_negotiated(h.client_id, GRANT_BYTES)
            h.store.add_peer_negotiated(self.client.client_id, GRANT_BYTES)
            self.server.db.save_storage_negotiated(
                bytes(self.client.client_id), bytes(h.client_id),
                GRANT_BYTES)
        engine = self.client.engine
        if engine.backend.name != "tpu":
            raise RuntimeError(f"client backend is {engine.backend.name}")
        if engine.device_dedup is None:
            raise RuntimeError("client has no device dedup index")
        pipe = engine.backend.pipeline  # runs the kernel probes
        from backuwup_tpu.ops import scan_fused
        kernels = {
            "fused": bool(pipe.fused),
            "pallas_digest": bool(pipe.pallas_digest),
            "pool_digest": bool(pipe.pool_digest),
            "scan_variant": ("v2" if scan_fused._V2_SELECTED else "v1")
            if pipe.fused else "xla",
            "mesh_devices": int(engine.device_dedup.mesh.devices.size)}
        if not self.rehearse and not (pipe.fused and pipe.pallas_digest
                                      and pipe.pool_digest):
            raise RuntimeError(f"kernels not selected: {kernels}")
        return kernels

    def _on_event(self, ev) -> None:
        payload = ev.payload
        if ev.kind == "transfer" and payload.get("outcome") == "summary":
            self.summaries.append(payload)
        elif ev.kind in ("panic", "error") or (
                ev.kind == "message" and "fail" in payload.get("text", "")):
            # the client's own log lines, so a backup that fails says why
            print(f"[client {ev.kind}] {payload.get('text', '')}",
                  file=sys.stderr, flush=True)

    def stored_bytes(self) -> list:
        """Bytes each holder has persisted for the client, by its own
        books (``bytes_received``): shards, packfiles and index files."""
        out = []
        for h in self.holders:
            info = h.store.get_peer(self.client.client_id)
            out.append(0 if info is None else int(info.bytes_received))
        return out

    async def stop(self) -> None:
        for a in [self.client] + self.holders:
            if a is not None:
                await a.stop()
        if self.server is not None:
            await self.server.stop()
