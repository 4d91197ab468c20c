"""The escalation-marking policy the benchmark serves with.

With random weights the edge model's uncertainty carries no signal, so
escalation is part of the traffic: the harness marks a seeded share of
requests, and this policy routes them.  ``assign`` sends an unmarked
request to the ``"edge"`` lane (its edge output is accepted) and a marked
one to ``"collab"``; ``decide`` gives every collaborative request the
mix's escalation action.  ``feedback`` keeps each escalated request's
discarded edge draft, which the correctness check compares too.
"""
from __future__ import annotations

from typing import Dict, List, Set

from repro.core.policy import CollabPolicy


class MarkedEscalation(CollabPolicy):
    name = "marked"

    def __init__(self, action: str):
        self.action = action
        self.marked: Set[int] = set()
        self.drafts: Dict[int, List[int]] = {}

    def mark(self, rid: int) -> None:
        self.marked.add(rid)

    def assign(self, features):
        return "collab" if features["rid"] in self.marked else "edge"

    def decide(self, unc, steps, budget):
        return [self.action] * len(unc)

    def feedback(self, action, quality, cost, features=None):
        if features and features.get("draft") is not None:
            self.drafts[features["rid"]] = list(features["draft"])
