"""The generated refusal matrix (theanompi_tpu/analysis/refusals.py
→ docs/REFUSALS.md): the inventory finds the tree's known refusals,
classifies bare raises as abstract slots, and the checked-in doc is
BYTE-IDENTICAL to a fresh render — adding/removing/rewording a
``raise NotImplementedError`` without regenerating the doc fails
here (ROADMAP item 4's matrix, machine-maintained).
"""

from pathlib import Path

from theanompi_tpu.analysis import refusals

ROOT = Path(__file__).resolve().parent.parent


def entries():
    return refusals.collect(ROOT)


class TestInventory:
    def test_known_refusals_present(self):
        msgs = [
            (e["module"], e["message"] or "") for e in entries()
            if e["message"] is not None
        ]
        # the ROADMAP item-4 matrix, found from the code itself
        # (the MoE x zero1 / compression pair is refused where the
        # exchange is planned, parallel/plan.py)
        assert any("plan" in m and "zero1" in t for m, t in msgs)
        assert any("plan" in m and "compression" in t.lower()
                   for m, t in msgs)
        assert any("llama" in m and "dropless" in t for m, t in msgs)
        assert any("decoder" in m and "tensor parallelism" in t
                   for m, t in msgs)
        assert any("adapter" in m for m, t in msgs)

    def test_bare_raises_are_abstract_slots(self):
        abstract = [e for e in entries() if e["message"] is None]
        wheres = {e["where"] for e in abstract}
        # the TMModel interface hooks are slots, not refusals
        assert "TMModel.build_model" in wheres
        assert all(e["message"] is None for e in abstract)

    def test_sorted_and_stable(self):
        e1, e2 = entries(), entries()
        assert e1 == e2
        keys = [(e["module"], e["where"], e["message"] or "")
                for e in e1]
        assert keys == sorted(keys)


class TestDocSync:
    def test_doc_matches_code(self):
        doc = (ROOT / refusals.DOC_REL).read_text()
        fresh = refusals.render(entries())
        assert doc == fresh, (
            "docs/REFUSALS.md is stale — regenerate with "
            "`python -m theanompi_tpu.analysis --write-refusals`"
        )

    def test_counts_in_headers(self):
        doc = (ROOT / refusals.DOC_REL).read_text()
        n_refusals = sum(
            1 for e in entries() if e["message"] is not None
        )
        assert f"## Declared refusals ({n_refusals})" in doc
