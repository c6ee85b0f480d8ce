"""The program's append-only log file with an offset index
(``LogFileKV``), kept in ``directory``: one under ``bench/.run/`` for
each cell and seed, which ``Cell.close`` removes.  ``{"kind": "logfile"}``"""
from __future__ import annotations


def make(spec: dict, directory):
    from repro.storage.kv import LogFileKV
    return LogFileKV(str(directory))
