"""Application startup: wire configs, loader, watchdog, services, HTTP app.

Parity with the reference's startup sequence (reference: core/startup/
startup.go:20-183 — dir creation, model install, config load, watchdog
start, warmup loads, shutdown hook).
"""

from __future__ import annotations

import asyncio
import logging
import os
import signal
from typing import Optional

from localai_tpu.capabilities import Capabilities, build_model_options
from localai_tpu.config.app_config import AppConfig
from localai_tpu.config.model_config import scan_models_dir
from localai_tpu.modelmgr.loader import ModelLoader
from localai_tpu.modelmgr.watchdog import WatchDog

log = logging.getLogger("localai_tpu.startup")


def startup(app_config: AppConfig):
    """Returns (Capabilities, ModelLoader, gallery_service)."""
    os.makedirs(app_config.models_path, exist_ok=True)

    if app_config.preload_models:
        from localai_tpu.gallery.preload import install_models

        install_models(app_config.preload_models, app_config.models_path,
                       app_config.galleries)

    configs = scan_models_dir(app_config.models_path)
    log.info("loaded %d model configs from %s", len(configs), app_config.models_path)

    loader = ModelLoader(single_active=app_config.single_active_backend)
    if app_config.enable_watchdog_idle or app_config.enable_watchdog_busy:
        wd = WatchDog(
            loader,
            busy_timeout_s=app_config.watchdog_busy_timeout_s,
            idle_timeout_s=app_config.watchdog_idle_timeout_s,
            check_busy=app_config.enable_watchdog_busy,
            check_idle=app_config.enable_watchdog_idle,
        )
        loader.watchdog = wd
        wd.start()

    caps = Capabilities(app_config, loader, configs)

    # warmup loads (reference: LoadToMemory, startup.go:148-176)
    for name in app_config.load_to_memory:
        mc = caps.resolve(name)
        try:
            caps._load(mc)
            log.info("warmed up model %s", name)
        except Exception:
            log.exception("warmup load failed for %s", name)

    from localai_tpu.services.gallery_service import GalleryService

    gallery_service = GalleryService(app_config, caps)
    gallery_service.start()

    # dynamic config hot-reload (reference: config_file_watcher.go:29-43)
    if app_config.dynamic_config_dir:
        from localai_tpu.config.watcher import ConfigWatcher

        ConfigWatcher(app_config, loader).start()
    return caps, loader, gallery_service


async def serve(app_config: AppConfig):
    from localai_tpu.api.app import build_app, run_app

    caps, loader, gallery_service = startup(app_config)
    app = build_app(caps, app_config, gallery_service)
    runner = await run_app(app, app_config.address)
    log.info("localai-tpu listening on %s", app_config.address)
    # SIGTERM stops the server the way Ctrl-C does: through the finally
    # below, which stops the backends. A server killed without it leaves
    # its runner orphaned (backends run in their own sessions) — and an
    # orphaned runner keeps the chip, so the next start finds none.
    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, stop.set)
    try:
        await stop.wait()
    finally:
        await runner.cleanup()
        gallery_service.shutdown()
        loader.stop_all()
