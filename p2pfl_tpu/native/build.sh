#!/bin/sh
# Build the native codec shared library next to this script, with the
# source-hash stamp the loader (native/__init__.py) checks before loading.
set -e
cd "$(dirname "$0")"
g++ -O3 -shared -fPIC -o libp2tw.so codec.cpp
sha256sum codec.cpp | cut -d' ' -f1 > libp2tw.so.sha256
echo "built $(pwd)/libp2tw.so"
