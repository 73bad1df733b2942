from snappydata_tpu.cli import main

raise SystemExit(main())
