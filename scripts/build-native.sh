#!/usr/bin/env bash
# Build the native C++ tunnel libraries into native/build/.
set -euo pipefail
cd "$(dirname "$0")/../native"
mkdir -p build

# Each output is compiled under a temporary name and renamed into place: a
# process that opens the library while another checkout's test run or `make
# native` builds it never maps a half-written file.
trap 'rm -f build/.*.$$.tmp' EXIT
build() {  # build <output under build/> <g++ arguments...>
  local out="build/$1" tmp="build/.$1.$$.tmp"
  shift
  g++ "$@" -o "$tmp"
  mv -f "$tmp" "$out"
  echo "built native/$out"
}

build libtunnelframes.so -O2 -Wall -Wextra -shared -fPIC tunnel_frames.cc
build libtunnelarq.so -O2 -Wall -Wextra -shared -fPIC tunnel_arq.cc

if [[ "${1:-}" == "sanitize" ]]; then
  # ASan+UBSan self-test binaries (make native-san): the C++ analog of the
  # memory/UB safety Rust gives the reference codec for free.
  SAN="-O1 -g -Wall -Wextra -fsanitize=address,undefined -fno-sanitize-recover=all"
  build tunnel_frames_test $SAN tunnel_frames.cc tunnel_frames_test.cc
  build tunnel_arq_test $SAN tunnel_arq.cc tunnel_arq_test.cc
fi
