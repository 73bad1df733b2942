"""Device-side building blocks of the compiled plans: the packed
reduction strategies (`reduction`), the code- and run-space aggregate
lanes (`code_agg`) and the device join (`join`). Import from submodules."""
