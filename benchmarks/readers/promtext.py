"""Parsing of the Prometheus text the program's registries render."""

import re

_LINE = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def parse(text: str) -> list:
    """[(series, {label: value}, number)] of every sample line."""
    out = []
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line.strip())
        if m:
            out.append((m.group(1), dict(_LABEL.findall(m.group(2) or "")),
                        float(m.group(3))))
    return out


def total(text: str, series: str, labels: dict) -> float:
    return sum(v for name, lab, v in parse(text) if name == series
               and all(lab.get(k) == str(want) for k, want in labels.items()))


def delta(ctx: dict, series: str, labels: dict) -> float:
    return (total(ctx["metrics_after"], series, labels)
            - total(ctx["metrics_before"], series, labels))
