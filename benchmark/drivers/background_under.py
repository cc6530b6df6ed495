"""Combinator: one driver's window runs in the background for as long as
another's runs in the foreground.  Both are whole drivers with their own
parameters, so the next "X under Y" mix is a data file."""

from __future__ import annotations

import threading

from . import make


class Driver:
    def __init__(self, params: dict, run):
        self.fg = make(params["foreground"], run)
        self.bg = make(params["background"], run)
        self.run = run
        self.bg_error = None

    def prepare(self) -> None:
        self.fg.prepare()
        self.bg.prepare()

    def warm(self, cluster) -> None:
        self.bg.warm(cluster)
        self.fg.warm(cluster)

    def _background(self, cluster, seconds: float) -> None:
        try:
            self.bg.run_window(cluster, seconds)
        except Exception as e:  # noqa: BLE001 — re-raised by run_window
            self.bg_error = e

    def run_window(self, cluster, seconds: float) -> None:
        th = threading.Thread(target=self._background,
                              args=(cluster, seconds))
        th.start()
        try:
            self.fg.run_window(cluster, seconds)
        finally:
            th.join()
        if self.bg_error is not None:
            raise self.bg_error

    def check_live(self, cluster) -> None:
        self.fg.check_live(cluster)
        self.bg.check_live(cluster)

    def check_files(self) -> None:
        self.fg.check_files()
        self.bg.check_files()
