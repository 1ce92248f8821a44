"""One package per model family, named by the config files' ``model_type``
(``chipbench/family.py`` states what each exposes)."""
