"""The runs a test drives through the ``transfer`` adapter's cells.

``tiny(cell)`` is the cell at a size a test can hold (the Pallas kernels
run in interpret mode on the CPU). ``CASES`` names each run: ``sound`` runs
must come out correct; the ``control`` (the configuration's integrity
guarantee switched off, or its digest taken on the host) and every
``fault`` that such a cell can have must come out not correct, each
through the check named beside it. A one-chip cell has no exchange between
chips to leave out.
"""
from __future__ import annotations

import copy
import dataclasses

OPEN_LOOP = {"loop": "open", "arrivals": {"law": "poisson", "rate_per_s": 4.0, "burst": 2},
             "tenants": {"law": "zipf", "count": 3, "s": 1.1}}


def tiny(cell):
    cfg = copy.deepcopy(cell.config)
    cfg["service"]["chunk_bytes"] = 1 << 15
    cfg["dataset"]["block_bytes"] = 1 << 12
    sizes = cfg["dataset"]["sizes"]
    if sizes["law"] == "fixed":
        sizes["bytes"] = 1 << 17
    else:
        cfg["dataset"]["files"] = 12
        sizes["max_bytes"] = 1 << 16
    cfg["warmup"]["file_bytes"] = [(1 << 15) - 1]
    traffic = dict(cell.traffic, files_per_request=min(3, cell.traffic["files_per_request"]))
    if traffic["loop"] == "closed":
        traffic["clients"] = min(2, traffic["clients"])
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def _open_loop(cell) -> None:
    cell.traffic = dict(cell.traffic, **OPEN_LOOP)
    cell.traffic.pop("clients", None)


def _service(**change):
    return lambda cell: cell.config["service"].update(change)


def _lands_nothing(mp):
    from repro.core.transfer import FileDest

    mp.setattr(FileDest, "write", lambda self, off, data: None)
    mp.setattr(FileDest, "writev", lambda self, off, views: sum(len(v) for v in views))


def _half_the_files(mp):
    from repro.service import TransferService

    submit = TransferService.submit
    mp.setattr(TransferService, "submit",
               lambda self, items, **kw: submit(self, items[:max(1, len(items) // 2)], **kw)
               if len(items) > 1 else [])


def _byte_flipped_unverified(mp):
    from repro.core import dataplane
    from repro.core.transfer import FileDest

    write = FileDest.write

    def flip(self, off, data):
        b = bytearray(data)
        b[0] ^= 0xFF
        write(self, off, bytes(b))

    mp.setattr(FileDest, "write", flip)
    mp.setattr(dataplane, "verify", lambda expected, actual: True)


def _last_byte_flipped_after_verify(mp):
    # silent corruption of a landing after it was verified and journaled, in
    # every request but the first: only a check of every request sees it
    from repro.service import TransferService

    wait_all = TransferService.wait_all

    def flip(self, task_ids, timeout=None):
        out = wait_all(self, task_ids, timeout)
        for st in out:
            for rep in st.item_reports:
                if "r000000" not in rep.dst and not rep.dst.endswith(".dst"):
                    with open(rep.dst, "r+b") as fh:
                        fh.seek(rep.nbytes - 1)
                        last = fh.read(1)[0]
                        fh.seek(rep.nbytes - 1)
                        fh.write(bytes([last ^ 1]))
        return out

    mp.setattr(TransferService, "wait_all", flip)


CASES = {
    # name: (kind, change to the tiny cell, fault planted by monkeypatch,
    #        check that must trip)
    "sound": ("sound", None, None, None),
    "sound_open_loop": ("sound", _open_loop, None, None),
    "control_integrity_off": ("control", _service(integrity=False), None,
                              "device_bytes_short"),
    "control_host_digest": ("control", _service(integrity_backend="host"), None,
                            "engine_host_bytes"),
    "state_unchanged": ("fault", None, _lands_nothing, "tasks_not_succeeded"),
    "landing_altered_verified": ("fault", None, _last_byte_flipped_after_verify,
                                 "files_differing"),
    "half_the_batch": ("fault", None, _half_the_files, "files_unreported"),
    "answer_altered": ("fault", None, _byte_flipped_unverified, "files_differing"),
}
