"""Find a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` at the checkout's root names every cell, configuration
and metric. Each of them lives in a file of its own under this
directory, found by its name:

- ``configs/<file>`` as the configuration's ``file`` entry says; its
  ``kind`` names the general generator ``gen_<kind>.py`` that drives it;
- ``traffic/<traffic>.json``: the parameters that generator reads;
- ``e2e_metrics/<name>.py`` and ``layer_metrics/<name>.py``: a reader
  ``read(run) -> float | None`` of one metric; ``None`` means the run
  gave it nothing to read, and the metric is left out of the line.

A later cell, mix, metric or kind of configuration is added by adding
such a file and an entry, with no edit to this module or the harness.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def check_name(name: str) -> str:
    """``name`` if it is a legal name; else ``ValueError``."""
    if not isinstance(name, str) or not NAME.fullmatch(name):
        raise ValueError(f"illegal name {name!r}: want 1-64 of [A-Za-z0-9_.-], "
                         f"not starting with '.' or '-'")
    return name


class Registry:
    """One benchmark description and the directory that holds its files."""

    def __init__(self, bench: dict, root: Path = HERE):
        self.bench = bench
        self.root = Path(root)

    @classmethod
    def load(cls, path: Path, root: Path = HERE) -> "Registry":
        return cls(json.loads(Path(path).read_text()), root)

    def _entry(self, section: str, name: str) -> dict:
        check_name(name)
        for e in self.bench.get(section, ()):
            if e["name"] == name:
                return e
        raise KeyError(f"no {section[:-1]} named {name!r} in BENCHMARK.json")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        """The configuration file's contents, with its entry as ``_entry``."""
        entry = self._entry("configs", name)
        # the entry names the file from the checkout's root
        cfg = json.loads((self.root.parents[1] / entry["file"]).read_text())
        cfg["_entry"] = entry
        return cfg

    def traffic(self, name: str) -> dict:
        return json.loads((self.root / "traffic" / f"{check_name(name)}.json")
                          .read_text())

    def metrics(self, section: str, cell: str) -> list[dict]:
        """The metrics of ``section`` (``end_to_end`` / ``per_layer``)
        that cell ``cell`` reports."""
        return [m for m in self.bench.get(section, ())
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, section: str, name: str):
        """The ``read`` function of one metric's reader file."""
        sub = {"end_to_end": "e2e_metrics", "per_layer": "layer_metrics"}[section]
        return self._module(f"{sub}/{check_name(name)}.py").read

    def generator(self, kind: str):
        """The general generator module ``gen_<kind>.py`` of a kind of
        configuration; it defines ``Driver(cfg, mix, seed)``."""
        return self._module(f"gen_{check_name(kind)}.py")

    def _module(self, rel: str):
        path = self.root / rel
        tag = re.sub(r"\W", "_", rel[:-3])
        spec = importlib.util.spec_from_file_location(f"_bench_{tag}", path)
        if spec is None or not path.is_file():
            raise KeyError(f"no file {rel} under {self.root}")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
